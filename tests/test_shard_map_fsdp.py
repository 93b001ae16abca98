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


# (case, mesh, device count)
_EXCHANGE_MESHES = [
    ("fsdp2", MeshConfig(data=1, fsdp=2), 2),
    ("fsdp4", MeshConfig(data=1, fsdp=4), 4),
    ("fsdp8", MeshConfig(data=1, fsdp=8), 8),
    ("data2_x_fsdp4", MeshConfig(data=2, fsdp=4), 8),
    # an axis of one: nothing to send, the partials pass through
    ("data2_x_fsdp1", MeshConfig(data=2, fsdp=1), 2),
]
# (case, [(full shape, axis sharded over 'fsdp' or None)], dtype)
_EXCHANGE_LEAVES = [
    ("axis0", [((16, 6), 0)], "float32"),
    ("axis1", [((6, 32), 1)], "float32"),
    ("several_widths_and_a_replicated_leaf",
     [((3, 8, 16), 2), ((5,), None), ((16, 64), 1), ((64, 8), 1), ((8, 24), 0)], "float32"),
    ("bf16", [((8, 16), 1), ((16, 8), 0)], "bfloat16"),
]


@pytest.mark.parametrize("leaves, dtype", [c[1:] for c in _EXCHANGE_LEAVES],
                         ids=[c[0] for c in _EXCHANGE_LEAVES])
@pytest.mark.parametrize("mesh_cfg, n_dev", [c[1:] for c in _EXCHANGE_MESHES],
                         ids=[c[0] for c in _EXCHANGE_MESHES])
def test_gradient_exchange_is_psum_scatter(mesh_cfg, n_dev, leaves, dtype):
    """The authored cross-chip gradient sum (GradExchange: n-1 ppermutes of
    one packed buffer, summed in float32) against `jax.lax.psum_scatter`
    over 'fsdp', every chip holding its own partials: a leaf sharded on
    axis 0, on axis 1, several leaves of different widths in one exchange, a
    replicated leaf (untouched), bf16 in and out. Small whole numbers, so
    any order of summation is exact. With a 'data' axis the sums a
    parameter replicated over it is owed are psummed over it as well."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from midgpt_tpu.parallel.shard_map_fsdp import GradExchange

    mesh = make_mesh(mesh_cfg, devices=jax.devices()[:n_dev])
    every = ("data", "fsdp")
    specs = [P(*["fsdp" if i == ax else None for i in range(len(shape))]) for shape, ax in leaves]
    rng = np.random.default_rng(0)
    partials = [
        jnp.asarray(rng.integers(-3, 4, (n_dev, *shape)), dtype) for shape, _ in leaves
    ]

    def body(*gs):
        gs = [g[0] for g in gs]
        want = [
            g if ax is None else jax.lax.psum_scatter(g, "fsdp", scatter_dimension=ax, tiled=True)
            for g, (_, ax) in zip(gs, leaves)
        ]
        exchange = GradExchange(specs)
        got = exchange.finish(exchange.stage(gs), want)
        # what a parameter replicated over 'data' is owed
        over_data = [jax.lax.psum(w, "data") for w in want]
        got_over_data = exchange.finish(exchange.stage(gs), over_data)
        return jax.tree.map(lambda a: a[None], (want, got, over_data, got_over_data))

    want, got, over_data, got_over_data = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(every), out_specs=P(every))
    )(*partials)
    for w, g, (shape, ax) in zip(want + over_data, got + got_over_data, leaves + leaves):
        assert g.dtype == w.dtype == jnp.dtype(dtype) and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))


@pytest.mark.parametrize("scan_unroll", [1, 2])
def test_carried_backward_puts_each_layers_gradient_in_its_own_row(scan_unroll):
    """The authored backward over the stack carries layer l's partial
    gradients into iteration l-1 and writes their sums from there: every
    layer's gradient must land in the layer's OWN row of the stacked leaves
    (a shift by one is the bug this pins), the top layer's (peeled before
    the loop) and layer 0's (summed after it) included. Four layers against
    AD of the plain scan; the rows are told apart first."""
    import dataclasses

    cfg, mesh, params, specs, xg, yg = _setup()
    cfg = dataclasses.replace(cfg, n_layer=4, scan_unroll=scan_unroll)
    params = GPT.init(cfg, jax.random.PRNGKey(1))
    specs = fsdp_param_specs(params, mesh, shard_model=True, min_size=0)
    params = jax.jit(lambda p: constrain(p, specs, mesh))(params)

    def plain_loss(p, x, y):
        return fused_linear_cross_entropy(GPT.hidden(cfg, p, x, inference=True), p.lm_head, y, CHUNK)

    sm_loss = make_shard_map_loss(cfg, mesh, specs, CHUNK)
    want = jax.jit(jax.grad(plain_loss))(params, xg, yg).blocks
    got = jax.jit(jax.grad(lambda p, x, y: sm_loss(p, x, y, None)))(params, xg, yg).blocks
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        w, g = np.asarray(w), np.asarray(g)
        assert w.shape[0] == 4
        for a in range(4):
            for b in range(a + 1, 4):
                assert not np.allclose(w[a], w[b], atol=1e-5, rtol=1e-4), "rows cannot be told apart"
            np.testing.assert_allclose(g[a], w[a], atol=1e-5, rtol=1e-4, err_msg=f"row {a}")


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
        "gspmd_tp": base.replace(fsdp_mode="gspmd"),
        "fsdp_only": base.replace(
            fsdp_mode="gspmd", mesh=MeshConfig(data=2, fsdp=4, sp=1)
        ),
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


# ---- the derived schedule (ExperimentConfig.fsdp_schedule) ----

_BASE = dict(
    rundir="", data_dir="", learning_rate=1e-3, batch_size=8, warmup_steps=2,
    min_lr=1e-4, lr_decay_steps=10, max_steps=10, beta2=0.95,
    weight_decay=1e-4, eval_interval=5, param_dtype="float32",
    compute_dtype="float32", g_accum_iters=1, shard_model=True, fsdp_min_size=0,
)
_TINY = GPTConfig(block_size=32, vocab_size=64, n_layer=2, n_head=2, n_embd=32)
_MOE = GPTConfig(
    block_size=32, vocab_size=64, n_layer=2, n_head=2, n_embd=32,
    n_experts=4, moe_top_k=2,
)


def _shape(**axes):
    return {a: axes.get(a, 1) for a in ("data", "fsdp", "sp", "tp", "pp", "ep")}


def _kimi_tiny():
    from test_kimi_linear import tiny_experiment

    return tiny_experiment()


# (case, config kwargs or a builder, mesh shape, schedule taken)
_RULE = [
    ("pure_fsdp", dict(model_config=_TINY), _shape(fsdp=4), "authored"),
    ("data_x_fsdp", dict(model_config=_TINY), _shape(data=2, fsdp=4), "authored"),
    ("data_parallel_only", dict(model_config=_TINY), _shape(data=8), "authored"),
    ("one_device", dict(model_config=_TINY), _shape(), "compiler"),
    ("fsdp_x_sp_ring",
     dict(model_config=GPTConfig(block_size=32, vocab_size=64, n_layer=2, n_head=2,
                                 n_embd=32, attn_impl="ring")),
     _shape(fsdp=2, sp=2), "authored"),
    ("fsdp_x_tp", dict(model_config=_TINY), _shape(fsdp=2, tp=2), "authored"),
    ("tp_x_sp", dict(model_config=_TINY), _shape(fsdp=2, sp=2, tp=2), "compiler"),
    ("tp_with_ring_attention",
     dict(model_config=GPTConfig(block_size=32, vocab_size=64, n_layer=2, n_head=2,
                                 n_embd=32, attn_impl="ring")),
     _shape(fsdp=2, tp=2), "compiler"),
    ("pipeline", dict(model_config=_TINY), _shape(fsdp=2, pp=2), "compiler"),
    ("expert_parallel", dict(model_config=_MOE), _shape(fsdp=2, ep=2), "compiler"),
    ("moe_without_aux", dict(model_config=_MOE), _shape(fsdp=4), "authored"),
    ("moe_aux_loss", dict(model_config=_MOE, moe_aux_coef=0.01), _shape(fsdp=4), "compiler"),
    ("kimi_linear_family", _kimi_tiny, _shape(data=8), "compiler"),
    ("kimi_linear_one_device", _kimi_tiny, _shape(), "compiler"),
    # the parity tests' handle forces either side, whatever the mesh
    ("forced_gspmd", dict(model_config=_TINY, fsdp_mode="gspmd"), _shape(fsdp=4), "compiler"),
    ("forced_shard_map_one_device", dict(model_config=_TINY, fsdp_mode="shard_map"),
     _shape(), "authored"),
]


@pytest.mark.parametrize(
    "make, mesh_shape, want", [r[1:] for r in _RULE], ids=[r[0] for r in _RULE]
)
def test_fsdp_schedule_is_derived_from_mesh_and_model(make, mesh_shape, want):
    """(mesh shape, pp, ep, tp x sp, moe_aux_coef, model family) -> the
    schedule the step takes. A configuration that does not mention fsdp_mode
    gets the authored ZeRO-3 schedule wherever it composes on more than one
    device, and the compiler's everywhere else: every one-device mesh, the
    pipeline, expert parallelism, the aux loss, tp together with sequence
    parallelism, another model family."""
    config = make() if callable(make) else ExperimentConfig(**_BASE, **make)
    assert config.fsdp_schedule(mesh_shape) == want


@pytest.mark.parametrize(
    "kw, match",
    [
        (dict(model_config=_TINY, mesh=MeshConfig(fsdp=2, pp=2)), "pp"),
        (dict(model_config=_MOE, mesh=MeshConfig(fsdp=2, ep=2)), "ep"),
        (dict(model_config=_MOE, moe_aux_coef=0.01), "moe_aux_coef"),
        (dict(model_config=_TINY, mesh=MeshConfig(fsdp=2, sp=2, tp=2)), "sequence parallelism"),
    ],
    ids=["pp", "ep", "aux", "tp_x_sp"],
)
def test_forcing_the_authored_schedule_where_it_does_not_compose_raises(kw, match):
    """What 'auto' falls back on, fsdp_mode='shard_map' refuses: the one
    statement of the rule (ExperimentConfig.authored_fsdp_refusal)."""
    ExperimentConfig(**_BASE, **kw)  # derived: constructs, takes the compiler's
    with pytest.raises(ValueError, match=match):
        ExperimentConfig(**_BASE, fsdp_mode="shard_map", **kw)


def test_forcing_the_authored_schedule_on_another_family_raises():
    config = _kimi_tiny()
    with pytest.raises(ValueError, match="model family"):
        config.replace(fsdp_mode="shard_map")


def _traced_step(config, devices):
    """jaxpr text of the train step on a mesh over `devices`, abstract state."""
    from midgpt_tpu.training.optim import make_optimizer
    from midgpt_tpu.training.train import make_train_step

    mesh = make_mesh(config.mesh, devices=devices)
    model = config.model_config.model()
    abstract = jax.eval_shape(lambda k: model.init(config.model_config, k), jax.random.PRNGKey(0))
    specs = model.param_specs(config, abstract, mesh)
    optimizer, _ = make_optimizer(config)
    step, *_ = make_train_step(config, optimizer, mesh, specs)
    G, B, T = config.g_accum_iters, config.batch_size, config.model_config.block_size
    tokens = jax.ShapeDtypeStruct((G, B, T), np.int32)
    return str(
        step.trace(
            abstract, jax.eval_shape(optimizer.init, abstract), tokens, tokens,
            jax.random.PRNGKey(0),
        ).jaxpr
    )


@pytest.mark.parametrize("family", ["gpt", "kimi_linear"])
def test_one_device_step_holds_no_shard_map_of_the_loss(family):
    """The guard for `train_124m` and `train_kimi_linear_t8k`: on a
    one-device mesh the derived schedule is the compiler's, so the traced
    step is the program it was before the schedule was derived — no
    shard_map anywhere in it (the GPT's four-device trace below has one,
    so the probe can see it). The four-device trace sums its block and
    lm_head gradients with ppermutes; the one reduce_scatter left is wte's."""
    if family == "gpt":
        config = ExperimentConfig(**_BASE, model_config=_TINY, mesh=MeshConfig(fsdp=1))
    else:
        config = _kimi_tiny()
    one = _traced_step(config, jax.devices()[:1])
    # ... and nothing of the authored gradient sum: no ppermute, no
    # custom_vjp of the layer stack (the stack is `lax.scan` and its AD)
    assert not [w for w in ("shard_map", "ppermute", "custom_vjp") if w in one]
    if family == "gpt":
        four = _traced_step(config.replace(mesh=MeshConfig(fsdp=4)), jax.devices()[:4])
        assert "shard_map" in four and "ppermute" in four
        assert four.count("reduce_scatter[") == 1  # wte's, once in the microstep loop


def test_runtime_reports_the_schedule_it_took(tmp_path, capsys):
    """TrainRuntime.fsdp_schedule, make_runtime's one log line and the flight
    recorder's gauge all say which loss make_train_step picked."""
    from midgpt_tpu.obs import flight_recorder
    from midgpt_tpu.training.train import make_runtime

    rng = np.random.default_rng(0)
    for split in ("train", "val"):
        rng.integers(0, 64, 4096, dtype=np.uint16).tofile(tmp_path / f"{split}.bin")
    base = ExperimentConfig(**{**_BASE, "data_dir": str(tmp_path)}, model_config=_TINY)
    for mesh_cfg, devices, want in (
        (MeshConfig(fsdp=4), jax.devices()[:4], "authored"),
        (MeshConfig(fsdp=1), jax.devices()[:1], "compiler"),
    ):
        rt = make_runtime(base.replace(mesh=mesh_cfg), devices=devices)
        assert rt.fsdp_schedule == want
        assert f"fsdp schedule: {want}" in capsys.readouterr().out
        gauges = flight_recorder().metrics.snapshot()["gauges"]
        assert gauges["fsdp.schedule_authored"] == float(want == "authored")
        # n-1 authored ppermutes a layer where the schedule is authored
        assert gauges["fsdp.grad_ring_hops"] == (3.0 if want == "authored" else 0.0)


# ---- parity at the four-chip cell's numerics ----


def _sgd_step_grads(config, x, y):
    """(loss, f32 gradient tree) of ONE real train step, read back through
    plain SGD at rate 1: new = old - grad."""
    import optax

    from midgpt_tpu.training.train import init_state, make_train_step

    mesh = make_mesh(config.mesh, devices=jax.devices()[:4])
    params, _, specs, _ = init_state(config, mesh)
    optimizer = optax.sgd(1.0)
    step, *_ = make_train_step(config, optimizer, mesh, specs)
    before = jax.tree.map(np.asarray, params)
    spec = batch_spec(shard_seq=mesh.shape["sp"] > 1)
    xg = make_global_batch(x, mesh, spec)
    yg = make_global_batch(y, mesh, spec)
    after, _, loss = step(params, optimizer.init(params), xg, yg, jax.random.PRNGKey(0))
    grads = jax.tree.map(lambda b, a: b - np.asarray(a), before, after)
    return float(loss), grads


@pytest.mark.parametrize(
    "mesh_cfg, attn_impl",
    [(MeshConfig(data=1, fsdp=4), "naive"), (MeshConfig(data=1, fsdp=2, sp=2), "ring"),
     (MeshConfig(data=1, fsdp=2, sp=2), "ulysses")],
    ids=["fsdp4", "fsdp2_x_sp2_ring", "fsdp2_x_sp2_ulysses"],
)
def test_schedules_agree_at_the_cell_numerics_bf16_dots_remat_g2(mesh_cfg, attn_impl):
    """`train_xl_fsdp4`'s numerics at a toy size: bf16 compute over f32 master
    weights, remat_policy='dots', G=2 accumulated in f32, fsdp=4. Loss and f32
    gradients of the authored schedule against the compiler's, both read off
    the REAL step (make_train_step), plus each against the float32-compute
    gradient of the same step.

    Where the tolerance comes from: the f32 case above holds the two
    schedules to rtol 1e-4 + atol 1e-5, about 1.7e3 float32 epsilons; that
    many bfloat16 epsilons (2^-8) would be 6.5 and say nothing. So the bound
    is relative to what bf16 itself costs: per leaf, the two schedules may
    differ (Frobenius norm, relative) by no more than 1.5 times the larger of
    their own distances to the float32-compute gradient — the choice of
    schedule stays inside the rounding noise the precision already has — and
    by no more than 16 bf16 epsilons (6.25e-2) outright. Readings on the CPU
    mesh (a correctness check): schedules apart 0.9e-2 to 2.5e-2 a leaf, each
    0.9e-2 to 2.5e-2 from float32 (two independent roundings of one
    gradient); losses apart 5.3e-5 relative. The authored schedule sums the bf16-rounded
    per-chip partials of a block leaf and of lm_head in its own exchange
    (parallel/shard_map_fsdp.py: bf16 on the wire, added in float32, rounded
    to bf16 once; wte's in a bf16 reduce-scatter), where the compiler's own
    gradient all-reduces at the cell's shapes are bf16 throughout (PERF.md
    section 6, PR 29 and PR 44). G=2 runs the whole of the carried backward
    twice (the peeled top layer, layer 0's exchange after the loop) and
    accumulates in float32 across the microsteps; with an `sp` axis the same
    body holds the ring / ulysses attention collectives, and the sums a
    weight is owed go over `sp` as well."""
    rng = np.random.default_rng(3)
    cfg = dict(
        **{**_BASE, "g_accum_iters": 2, "compute_dtype": "bfloat16"},
        mesh=mesh_cfg,
        model_config=GPTConfig(
            block_size=64, vocab_size=256, n_layer=2, n_head=2, n_embd=64,
            remat=True, remat_policy="dots", attn_impl=attn_impl,
        ),
    )
    x = rng.integers(0, 256, (2, 8, 64), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    loss_c, g_c = _sgd_step_grads(ExperimentConfig(fsdp_mode="gspmd", **cfg), x, y)
    loss_a, g_a = _sgd_step_grads(ExperimentConfig(fsdp_mode="shard_map", **cfg), x, y)
    _, g_f = _sgd_step_grads(
        ExperimentConfig(fsdp_mode="gspmd", **{**cfg, "compute_dtype": "float32"}), x, y
    )
    assert abs(loss_a - loss_c) <= 2 ** -8 * abs(loss_c)

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(g_c)]
    for path, c, a, f in zip(paths, *(jax.tree.leaves(g) for g in (g_c, g_a, g_f))):
        assert c.dtype == a.dtype == np.float32
        apart, noise = rel(a, c), max(rel(c, f), rel(a, f))
        assert apart <= min(1.5 * noise, 16 * 2 ** -8), (path, apart, noise)
