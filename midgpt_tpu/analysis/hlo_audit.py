"""graftcheck pass 2: compiled-artifact audits over post-optimization HLO.

Extends utils/hlo.py (the parser the structural test pins already share)
with reusable assertions that turn scheduling/parity *claims* into
executable checks:

  * `CompileCounter` — counts actual XLA backend compiles via the
    jax.monitoring event stream, so tests can pin "N request mixes -> 0 new
    compiles" (SERVING.md: admitting/finishing requests never recompiles)
    and "the train step compiles exactly once".
  * `jit_cache_size` (utils/hlo.py) — the jit wrapper's executable-cache population (one
    entry per compiled program), for pinning the *total* compile set of a
    module-level jit like sampling/serve._serve_decode_chunk.
  * `while_body_collectives` / `assert_no_while_body_collectives` — a
    collective census of while-loop bodies (transitive through called
    computations), e.g. "no all-gathers inside the decode while body".
  * `entry_parameter_dtypes` / `assert_fp32_master_params` — the SURVEY.md
    §7.4 precision contract (fp32 master params, bf16 compute cast in-step)
    read off the lowered train step instead of trusted from a docstring.

Everything here imports jax lazily so `python -m midgpt_tpu.analysis`
(pass 1) stays free of backend initialization.
"""

from __future__ import annotations

import re
import typing as tp

# jit_cache_size, pool_relayouts, weight_copies and rotary_gathers live in
# utils/hlo.py (the serving engine reads the first three and imports nothing
# of analysis/); they are this module's too.
from midgpt_tpu.utils.hlo import (
    hlo_computations,
    jit_cache_size,
    pool_relayouts,
    rotary_gathers,
    weight_copies,
    while_body_names,
)

# Event recorded once per actual XLA backend compilation (jax wraps
# backend.compile in record_event_duration_secs under this name).
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_COLLECTIVE_RE = re.compile(
    r"\b(" + "|".join(COLLECTIVE_OPS) + r")(?:-start|-done)?\("
)
# computations referenced by an instruction (fusions, while bodies, reducers)
_CALLEE_RE = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_ENTRY_HEADER_RE = re.compile(r"^ENTRY\s+%?[\w.\-]+\s*\((?P<args>.*)\)\s*->")
_PARAM_TYPE_RE = re.compile(r":\s*\(?([a-z]+[0-9]*)\[")


class CompileCounter:
    """Counts XLA backend compiles within a `with` block.

    Wraps the jax.monitoring duration-event stream (the hook jax's own
    compile path reports through), so cache hits — the thing the serving
    pins care about distinguishing — count zero."""

    def __init__(self) -> None:
        self.count = 0

    def _listener(self, name: str, duration: float, **kw: tp.Any) -> None:
        if name == BACKEND_COMPILE_EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc: tp.Any) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._listener)


# ----------------------------------------------------------------------
# HLO text audits
# ----------------------------------------------------------------------


def _reachable(comps: tp.Dict[str, tp.List[str]], root: str) -> tp.Set[str]:
    seen = {root}
    frontier = [root]
    while frontier:
        name = frontier.pop()
        for line in comps.get(name, ()):
            for callee in _CALLEE_RE.findall(line):
                if callee not in seen:
                    seen.add(callee)
                    frontier.append(callee)
    return seen


def while_body_collectives(
    hlo_text: str, ops: tp.Sequence[str] = COLLECTIVE_OPS
) -> tp.Dict[str, tp.List[str]]:
    """{while_body_computation: [collective instruction lines]}, transitive
    through computations the body calls (fusions, nested control flow)."""
    comps = hlo_computations(hlo_text)
    wanted = re.compile(r"\b(" + "|".join(ops) + r")(?:-start|-done)?\(")
    census: tp.Dict[str, tp.List[str]] = {}
    for body in sorted(while_body_names(hlo_text)):
        hits: tp.List[str] = []
        for comp in _reachable(comps, body):
            hits.extend(l for l in comps.get(comp, ()) if wanted.search(l))
        census[body] = hits
    return census


def while_body_pool_copies(
    hlo_text: str, shape: str
) -> tp.Dict[str, tp.List[str]]:
    """{while_body: [copy instruction lines producing `shape`]}, transitive
    through called computations — the zero-in-loop-cache-copy census. The
    serving engine's perf story rests on its KV pools aliasing through loop
    carries (decode chunk AND speculative verify): a pool-sized copy inside
    a while body means every loop iteration re-materializes the pool
    (2.5 ms/token measured when the r1-r4 decode structure did exactly
    that; measured on an earlier toolchain, not re-measured). `shape` is the
    literal HLO shape string, e.g.
    'f32[2,2,9,8,16]'. One-time entry copies OUTSIDE loop bodies are fine
    and not counted."""
    comps = hlo_computations(hlo_text)
    wanted = re.compile(rf"= {re.escape(shape)}[^=]*copy\(")
    census: tp.Dict[str, tp.List[str]] = {}
    for body in sorted(while_body_names(hlo_text)):
        hits: tp.List[str] = []
        for comp in _reachable(comps, body):
            hits.extend(l for l in comps.get(comp, ()) if wanted.search(l))
        census[body] = hits
    return census


_SHAPE_DIMS_RE = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")
_SCATTER_RE = re.compile(r"= ([a-z]+[0-9]*\[[0-9,]*\])\S* scatter\(")


def _shape_signature(shape: str) -> tp.Tuple[int, ...]:
    """Sorted dims: equal for a buffer, any transpose of it and any
    element type — the backend may scatter into a relaid-out, widened view
    of the pool (XLA CPU scatters a bf16 pool as f32)."""
    m = _SHAPE_DIMS_RE.match(shape)
    assert m, f"not an HLO shape string: {shape!r}"
    return tuple(sorted(int(d) for d in m.group(1).split(",") if d))


def pool_scatter_count(lines: tp.Iterable[str], shape: str) -> int:
    """Scatter instructions among `lines` that write a `shape`-sized
    buffer: the program's own K/V (or scale) writes — the one pool traffic
    a serving program is meant to have."""
    want = _shape_signature(shape)
    return sum(
        1
        for l in lines
        for m in [_SCATTER_RE.search(l)]
        if m and _shape_signature(m.group(1)) == want
    )


def while_body_pool_scatters(hlo_text: str, shape: str) -> tp.Dict[str, int]:
    """{while_body: pool_scatter_count}, transitive like the copy census."""
    comps = hlo_computations(hlo_text)
    return {
        body: sum(
            pool_scatter_count(comps.get(comp, ()), shape)
            for comp in _reachable(comps, body)
        )
        for body in sorted(while_body_names(hlo_text))
    }


def loop_pool_copy_excess(hlo_text: str, shape: str) -> tp.Dict[str, int]:
    """{while_body: pool-shaped copies BEYOND the lowering's per-scatter
    relayout allowance} — the aliasing census every serving program is held
    to (budgets.LOOP_POOL_COPY_BUDGET: zero). A body with no pool scatter
    gets no allowance at all, so the failure the census exists for — a pool
    re-materialized by the loop CARRY — still reads as an excess; what is
    forgiven is only what budgets.LOOP_POOL_COPIES_PER_SCATTER documents:
    the relayout this backend wraps around each in-loop scatter."""
    from midgpt_tpu.analysis import budgets

    copies = while_body_pool_copies(hlo_text, shape)
    scatters = while_body_pool_scatters(hlo_text, shape)
    return {
        body: max(
            0,
            len(lines) - budgets.LOOP_POOL_COPIES_PER_SCATTER * scatters[body],
        )
        for body, lines in copies.items()
    }


def _assert_pool_aliases(hlo_text: str, shape: str, what: str) -> tp.Dict[str, int]:
    """The report entry for one (program, buffer) aliasing census; raises
    when any loop body copies the buffer beyond the budget."""
    from midgpt_tpu.analysis import budgets

    excess = loop_pool_copy_excess(hlo_text, shape)
    assert all(n == budgets.LOOP_POOL_COPY_BUDGET for n in excess.values()), (
        f"{shape} copies inside {what} beyond the per-scatter allowance: "
        + str({b: n for b, n in excess.items() if n})
    )
    return excess


def assert_no_while_body_collectives(
    hlo_text: str, ops: tp.Sequence[str] = ("all-gather",)
) -> None:
    census = while_body_collectives(hlo_text, ops)
    offenders = {b: ls for b, ls in census.items() if ls}
    assert not offenders, (
        f"collectives {ops} found inside while bodies: "
        + "; ".join(f"{b}: {ls[0]}" for b, ls in offenders.items())
    )


def entry_parameter_dtypes(hlo_text: str) -> tp.List[str]:
    """Dtype strings of the ENTRY computation's parameters, in order."""
    for line in hlo_text.splitlines():
        m = _ENTRY_HEADER_RE.match(line.strip())
        if m:
            return _PARAM_TYPE_RE.findall(m.group("args"))
    raise ValueError("no ENTRY computation header found in HLO text")


def fp32_master_param_audit(hlo_text: str) -> tp.Dict[str, int]:
    """Counts used by assert_fp32_master_params (exposed for reporting)."""
    dtypes = entry_parameter_dtypes(hlo_text)
    return {
        "n_params": len(dtypes),
        "n_f32": sum(d == "f32" for d in dtypes),
        "n_reduced": sum(d in ("bf16", "f16") for d in dtypes),
        "has_bf16_compute": int(" bf16[" in hlo_text or "=bf16[" in hlo_text),
    }


def assert_fp32_master_params(
    hlo_text: str, expect_bf16_compute: bool = True
) -> tp.Dict[str, int]:
    """The SURVEY.md §7.4 precision contract on a lowered train step: every
    floating-point ENTRY parameter (master params + optimizer state) is f32
    — none arrive half-precision — while the program body still computes in
    bf16 (the per-step cast). Returns the audit counts."""
    audit = fp32_master_param_audit(hlo_text)
    assert audit["n_reduced"] == 0, (
        f"{audit['n_reduced']} reduced-precision entry parameters — master "
        "params/optimizer state must be fp32 (SURVEY.md §7.4)"
    )
    assert audit["n_f32"] > 0, "no f32 entry parameters found — wrong program?"
    if expect_bf16_compute:
        assert audit["has_bf16_compute"], (
            "no bf16 values anywhere in the program — the compute-dtype cast "
            "is missing (or the config under audit is not bf16-compute)"
        )
    return audit


# ----------------------------------------------------------------------
# built-in audit suite (CLI --audit)
# ----------------------------------------------------------------------


def run_audit() -> tp.Dict[str, tp.Any]:
    """Fast CPU-only audit of the two flagship compiled artifacts.

    Lowers (a) the train step of a tiny bf16-compute config and (b) the
    serving decode chunk, entirely against abstract inputs — no weights are
    materialized — then runs the fp32-master and while-body-collective
    audits. Returns a JSON-able report; raises AssertionError on violation.
    """
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.analysis import budgets
    from midgpt_tpu.config import ExperimentConfig, MeshConfig
    from midgpt_tpu.models.gpt import GPT, GPTConfig, PagedKVCache
    from midgpt_tpu.parallel.mesh import make_mesh
    from midgpt_tpu.utils.hlo import lower_abstract_train_step

    report: tp.Dict[str, tp.Any] = {"backend": jax.default_backend()}

    # All geometry and numeric budgets come from the declarative manifest
    # (analysis/budgets.py) — the same source tests/test_recompile_pins.py
    # asserts the report against, so audit and pins cannot drift.
    g = budgets.AUDIT
    mc = GPTConfig(
        block_size=g.block_size,
        vocab_size=g.vocab_size,
        n_layer=g.n_layer,
        n_head=g.n_head,
        n_embd=g.n_embd,
    )
    cfg = ExperimentConfig(
        rundir="",
        data_dir="",
        learning_rate=1e-3,
        batch_size=len(jax.devices()),
        warmup_steps=1,
        min_lr=1e-4,
        lr_decay_steps=10,
        max_steps=10,
        beta2=0.99,
        weight_decay=0.0,
        eval_interval=5,
        param_dtype="float32",
        compute_dtype="bfloat16",
        g_accum_iters=1,
        shard_model=True,
        fsdp_min_size=0,
        mesh=MeshConfig(data=-1, fsdp=-1),
        model_config=mc,
    )
    mesh = make_mesh(cfg.mesh)
    step_hlo = lower_abstract_train_step(cfg, mesh).compile().as_text()
    report["train_step_fp32_master"] = assert_fp32_master_params(step_hlo)

    # Decode program: the serving engine's fixed-shape decode chunk. Lowered
    # abstractly (eval_shape for params + paged cache); the while body (the
    # lax.scan over decode steps) must stay free of all-gathers — page
    # tables/lengths ride as plain jit inputs, nothing re-shards per step.
    from midgpt_tpu.sampling.serve import _serve_decode_chunk

    params_abs = jax.eval_shape(lambda k: GPT.init(mc, k), jax.random.PRNGKey(0))
    cache_abs = jax.eval_shape(
        lambda: PagedKVCache.init(
            mc, num_pages=g.num_pages, page_size=g.page_size, dtype=jnp.float32
        )
    )
    B, max_pages = g.batch, g.max_pages
    decode_hlo = (
        _serve_decode_chunk.lower(
            mc,
            params_abs,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            cache_abs,
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            g.decode_chunk,
            0.0,
            None,
            None,
            "gather",
            None,
        )
        .compile()
        .as_text()
    )
    assert_no_while_body_collectives(decode_hlo)
    census = while_body_collectives(decode_hlo)
    report["decode_while_bodies"] = {b: len(ls) for b, ls in census.items()}
    assert census, "decode program lowered without a while loop (scan vanished?)"

    # Zero-in-loop-cache-copy census: the KV pool must alias through the
    # decode loop's carry (the r5/r6 perf pin held by tests/test_sampling.py
    # on bigger shapes), here audited on the same artifact the collective
    # census reads.
    pool_shape = budgets.pool_shape(g)
    report["decode_loop_pool_copies"] = _assert_pool_aliases(
        decode_hlo, pool_shape, "the decode while body"
    )

    # Speculative verify program (sampling/serve.py _spec_verify_chunk):
    # same two audits. Lowered with decode_layer_scan=True so the layer
    # loop is a while body — the unrolled lowering has no loop at all (its
    # scatters alias the donated pool directly); the rolled scan is where
    # a carry-aliasing regression would surface as in-loop pool copies.
    import dataclasses

    from midgpt_tpu.sampling.serve import _spec_verify_chunk

    mc_scan = dataclasses.replace(mc, decode_layer_scan=True)
    K = g.spec_k
    verify_hlo = (
        _spec_verify_chunk.lower(
            mc_scan,
            params_abs,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((K, B), jnp.int32),
            jax.ShapeDtypeStruct((K, B, mc.vocab_size), jnp.float32),
            cache_abs,
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            0.0,
            None,
            None,
            "gather",
            None,
        )
        .compile()
        .as_text()
    )
    assert_no_while_body_collectives(verify_hlo)
    v_census = while_body_collectives(verify_hlo)
    report["verify_while_bodies"] = {b: len(ls) for b, ls in v_census.items()}
    assert v_census, "verify program lowered without its layer-scan while loop"
    report["verify_loop_pool_copies"] = _assert_pool_aliases(
        verify_hlo, pool_shape, "the verify layer loop"
    )

    # Int8 cache mode: the same zero-in-loop-copy property must hold for
    # the quantized pools AND their f32 scale side buffers (a scale-sized
    # copy per decode step would silently rebuild the side buffer every
    # token — small, but a per-token O(pool) cost of exactly the kind the
    # census exists to catch). Audited on all three serving programs:
    # decode, draft (the speculative proposer's scan of paged decode steps,
    # here a 1-layer prefix self-draft against the target pool), verify.
    from midgpt_tpu.sampling.serve import _spec_draft_chunk

    cache8_abs = jax.eval_shape(
        lambda: PagedKVCache.init(
            mc, num_pages=g.num_pages, page_size=g.page_size, dtype=jnp.int8
        )
    )
    pool8_shape = budgets.pool_shape(g, "s8")
    scale_shape = budgets.scale_shape(g)
    decode8_hlo = (
        _serve_decode_chunk.lower(
            mc,
            params_abs,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            cache8_abs,
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            g.decode_chunk,
            0.0,
            None,
            None,
            "gather",
            None,
        )
        .compile()
        .as_text()
    )
    draft_cfg = dataclasses.replace(mc, n_layer=g.draft_n_layer)
    draft_abs = jax.eval_shape(
        lambda k: GPT.init(draft_cfg, k), jax.random.PRNGKey(0)
    )
    # prefix self-draft: the draft runs against the TARGET pool's first
    # layer(s), exactly how ServeEngine(draft_shares_cache=True) calls it
    draft8_hlo = (
        _spec_draft_chunk.lower(
            draft_cfg,
            draft_abs,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            cache8_abs,
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            K,
            0.0,
            None,
            None,
            "gather",
            None,
        )
        .compile()
        .as_text()
    )
    verify8_hlo = (
        _spec_verify_chunk.lower(
            mc_scan,
            params_abs,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((K, B), jnp.int32),
            jax.ShapeDtypeStruct((K, B, mc.vocab_size), jnp.float32),
            cache8_abs,
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            0.0,
            None,
            None,
            "gather",
            None,
        )
        .compile()
        .as_text()
    )
    for name, hlo in (
        ("decode_int8", decode8_hlo),
        ("draft_int8", draft8_hlo),
        ("verify_int8", verify8_hlo),
    ):
        assert_no_while_body_collectives(hlo)
        assert while_body_names(hlo), f"{name} program lowered without a loop"
        for label, shape in (("pool", pool8_shape), ("scale", scale_shape)):
            report[f"{name}_loop_{label}_copies"] = _assert_pool_aliases(
                hlo, shape, f"the {name} loop"
            )

    # ------------------------------------------------------------------
    # split-K lowerings: partitioning must add zero pool traffic
    # ------------------------------------------------------------------
    # split_k > 1 partitions the attention softmax statistics over key
    # partitions (kernels/decode_attention.py gather paths; the Pallas
    # template's extra grid dimension on TPU). The audit claim: the split
    # lowering reads the pool through the same single gather as the
    # unsplit pass — it must not copy the pool (or, int8, the scale side
    # buffers) inside the decode loop, and it introduces no collectives
    # (the partial merge is per-slot elementwise math). Censused on the
    # same three serving programs as the unsplit audits, at split_k=4.
    split4_decode_hlo = (
        _serve_decode_chunk.lower(
            mc,
            params_abs,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            cache_abs,
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            g.decode_chunk,
            0.0,
            None,
            None,
            "gather",
            None,
            None,
            g.split_k,
        )
        .compile()
        .as_text()
    )
    assert_no_while_body_collectives(split4_decode_hlo)
    s_census = while_body_collectives(split4_decode_hlo)
    report["split_decode_while_bodies"] = {b: len(ls) for b, ls in s_census.items()}
    assert s_census, "split-K decode lowered without its while loops"
    report["split_decode_loop_pool_copies"] = _assert_pool_aliases(
        split4_decode_hlo, pool_shape, "the split-K decode loops"
    )

    split4_verify_hlo = (
        _spec_verify_chunk.lower(
            mc_scan,
            params_abs,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((K, B), jnp.int32),
            jax.ShapeDtypeStruct((K, B, mc.vocab_size), jnp.float32),
            cache_abs,
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            0.0,
            None,
            None,
            "gather",
            None,
            None,
            g.split_k,
        )
        .compile()
        .as_text()
    )
    assert_no_while_body_collectives(split4_verify_hlo)
    report["split_verify_loop_pool_copies"] = _assert_pool_aliases(
        split4_verify_hlo, pool_shape, "the split-K verify loops"
    )

    split4_decode8_hlo = (
        _serve_decode_chunk.lower(
            mc,
            params_abs,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            cache8_abs,
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            g.decode_chunk,
            0.0,
            None,
            None,
            "gather",
            None,
            None,
            g.split_k,
        )
        .compile()
        .as_text()
    )
    assert_no_while_body_collectives(split4_decode8_hlo)
    for label, shape in (("pool", pool8_shape), ("scale", scale_shape)):
        report[f"split_decode_int8_loop_{label}_copies"] = _assert_pool_aliases(
            split4_decode8_hlo, shape, "the split-K int8 decode loops"
        )

    # ------------------------------------------------------------------
    # fused multi-round group lowerings: k rounds, one pool carry
    # ------------------------------------------------------------------
    # Round-overlap dispatch's group lever (sampling/serve.py
    # _serve_decode_group; docs/SERVING.md "Round-overlap dispatch") wraps
    # round_group decode rounds in one lax.scan, so a single in-loop pool
    # copy would be paid n_steps * round_group times PER DISPATCH — the
    # census that caught the r1-r4 structure (measured on an earlier toolchain,
    # not re-measured) matters k
    # times more here. Lowered at every budgets.ROUND_GROUPS_AUDITED value
    # (f32) plus int8 at the smallest; the scan body is single-engine work
    # and must carry zero collectives of any kind.
    from midgpt_tpu.sampling.serve import _serve_decode_group

    for rg in budgets.ROUND_GROUPS_AUDITED:
        group_hlo = (
            _serve_decode_group.lower(
                mc,
                params_abs,
                jax.ShapeDtypeStruct((B,), jnp.int32),
                cache_abs,
                jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.bool_),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.bool_),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                g.decode_chunk,
                rg,
                0.0,
                None,
                None,
                "gather",
                None,
            )
            .compile()
            .as_text()
        )
        assert_no_while_body_collectives(group_hlo, ops=COLLECTIVE_OPS)
        g_census = while_body_collectives(group_hlo)
        report[f"group{rg}_decode_while_bodies"] = {
            b: len(ls) for b, ls in g_census.items()
        }
        assert g_census, f"group:{rg} decode lowered without its scan loop"
        report[f"group{rg}_decode_loop_pool_copies"] = _assert_pool_aliases(
            group_hlo, pool_shape, f"the group:{rg} decode scan body"
        )

    rg0 = budgets.ROUND_GROUPS_AUDITED[0]
    group8_hlo = (
        _serve_decode_group.lower(
            mc,
            params_abs,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            cache8_abs,
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            g.decode_chunk,
            rg0,
            0.0,
            None,
            None,
            "gather",
            None,
        )
        .compile()
        .as_text()
    )
    assert_no_while_body_collectives(group8_hlo, ops=COLLECTIVE_OPS)
    for label, shape in (("pool", pool8_shape), ("scale", scale_shape)):
        report[f"group{rg0}_decode_int8_loop_{label}_copies"] = _assert_pool_aliases(
            group8_hlo, shape, f"the group:{rg0} int8 scan body"
        )

    # ------------------------------------------------------------------
    # attention-variant lowerings: GQA/MQA pools, sliding-window masking
    # ------------------------------------------------------------------
    # GQA shrinks the pool's head axis to the KV-head count — a geometry
    # change, which is exactly the kind of edit that silently breaks the
    # donation/aliasing match the decode loop depends on — so the variant
    # lowerings must hold the same zero-in-loop-copy and collective-free
    # pins as MHA, with the census grepping the KV-head pool shape.
    # Window+sinks masking is select math on scores: it must add zero pool
    # traffic. Audited at AUDIT_GQA (MQA, the extreme grouping) and
    # AUDIT_GQA_WINDOW (same pools + window masking), f32 and int8.
    gv = budgets.AUDIT_GQA
    mc_gqa = GPTConfig(
        block_size=gv.block_size,
        vocab_size=gv.vocab_size,
        n_layer=gv.n_layer,
        n_head=gv.n_head,
        n_embd=gv.n_embd,
        n_kv_heads=gv.n_kv_heads,
    )
    gw = budgets.AUDIT_GQA_WINDOW
    mc_gqa_win = dataclasses.replace(
        mc_gqa, sliding_window=gw.sliding_window, attn_sinks=gw.attn_sinks
    )
    params_gqa_abs = jax.eval_shape(
        lambda k: GPT.init(mc_gqa, k), jax.random.PRNGKey(0)
    )
    cache_gqa_abs = jax.eval_shape(
        lambda: PagedKVCache.init(
            mc_gqa, num_pages=gv.num_pages, page_size=gv.page_size,
            dtype=jnp.float32,
        )
    )
    cache_gqa8_abs = jax.eval_shape(
        lambda: PagedKVCache.init(
            mc_gqa, num_pages=gv.num_pages, page_size=gv.page_size,
            dtype=jnp.int8,
        )
    )

    def _variant_decode_lower(cfg, cache):
        return _serve_decode_chunk.lower(
            cfg,
            params_gqa_abs,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            cache,
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            g.decode_chunk,
            0.0,
            None,
            None,
            "gather",
            None,
        ).compile().as_text()

    gqa_hlo = _variant_decode_lower(mc_gqa, cache_gqa_abs)
    gqa_win_hlo = _variant_decode_lower(mc_gqa_win, cache_gqa_abs)
    gqa8_hlo = _variant_decode_lower(mc_gqa, cache_gqa8_abs)
    gqa_pool = budgets.pool_shape(gv)
    for name, hlo in (("gqa", gqa_hlo), ("gqa_window", gqa_win_hlo)):
        assert_no_while_body_collectives(hlo, ops=COLLECTIVE_OPS)
        v_census = while_body_collectives(hlo)
        report[f"{name}_decode_while_bodies"] = {
            b: len(ls) for b, ls in v_census.items()
        }
        assert v_census, f"{name} decode lowered without its scan loop"
        report[f"{name}_decode_loop_pool_copies"] = _assert_pool_aliases(
            hlo, gqa_pool, f"the {name} decode loop"
        )
    assert_no_while_body_collectives(gqa8_hlo, ops=COLLECTIVE_OPS)
    for label, shape in (
        ("pool", budgets.pool_shape(gv, "s8")),
        ("scale", budgets.scale_shape(gv)),
    ):
        report[f"gqa_decode_int8_loop_{label}_copies"] = _assert_pool_aliases(
            gqa8_hlo, shape, "the int8 GQA decode loop"
        )

    # ------------------------------------------------------------------
    # tp serving mesh: per-program in-loop collective census
    # ------------------------------------------------------------------
    # The mesh-sharded engine's perf claim (docs/SERVING.md "Mesh-sharded
    # serving") is that tp decode pays ONLY the megatron activation
    # collectives — two all-reduces per layer per step, nothing else, and
    # in particular zero pool/scale traffic: the pools shard heads over
    # 'tp' and never cross shards. Audited on abstractly-lowered SHARDED
    # programs (ShapeDtypeStruct + NamedSharding; the partitioned modules
    # show per-shard pool shapes, which is what the copy census greps).
    # Budget per while body: 2 * n_layer all-reduces for the step-scan
    # programs (layers unrolled inside the body), 2 for the layer-scan
    # verify body (the body IS one layer), zero all-gather / all-to-all /
    # reduce-scatter / collective-permute anywhere in any loop.
    if len(jax.devices()) >= 2:
        from jax.sharding import NamedSharding

        from midgpt_tpu.parallel.serve_tp import (
            make_serve_mesh,
            serve_cache_specs,
            serve_param_specs,
        )

        smesh = make_serve_mesh(tp_size=g.tp)
        report["tp_mesh"] = budgets.tp_mesh_shape(g)
        # the configs as an engine is handed them: head-aligned qkv shards
        # need the split3 einsum order, and the serving layer loop takes it
        # under a tp > 1 mesh, scanned (`tp_verify`) or unrolled
        # (GPT._decode_layer_loop)

        def _shard_abs(tree, specs):
            return jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(
                    l.shape, l.dtype, sharding=NamedSharding(smesh, s)
                ),
                tree,
                specs,
            )

        params_tp = _shard_abs(params_abs, serve_param_specs(params_abs, smesh))
        draft_tp = _shard_abs(draft_abs, serve_param_specs(draft_abs, smesh))
        cache_tp = _shard_abs(cache_abs, serve_cache_specs(cache_abs))
        cache8_tp = _shard_abs(cache8_abs, serve_cache_specs(cache8_abs))
        sds = jax.ShapeDtypeStruct
        i32, b1 = jnp.int32, jnp.bool_

        def _decode_lower(cfg, cache, split_k=1):
            return _serve_decode_chunk.lower(
                cfg, params_tp, sds((B,), i32), cache,
                sds((B, max_pages), i32), sds((B,), i32), sds((B,), b1),
                g.decode_chunk, 0.0, None, None, "gather", None, smesh,
                split_k,
            ).compile().as_text()

        # One lowering per budgets.TP_PROGRAMS entry; the per-program
        # all-reduce budget comes from the manifest, not from literals here.
        tp_lowered = {
            "tp_decode": _decode_lower(mc, cache_tp),
            "tp_decode_int8": _decode_lower(mc, cache8_tp),
            # split-K under tp: the partition scan rides INSIDE each head
            # shard — the all-reduce budget must not move by a single op
            "tp_decode_split": _decode_lower(mc, cache_tp, split_k=g.split_k),
            "tp_verify": _spec_verify_chunk.lower(
                mc_scan, params_tp, sds((B,), i32), sds((K, B), i32),
                sds((K, B, mc.vocab_size), jnp.float32), cache_tp,
                sds((B, max_pages), i32), sds((B,), i32), sds((B,), b1),
                0.0, None, None, "gather", None, smesh,
            ).compile().as_text(),
            "tp_draft_int8": _spec_draft_chunk.lower(
                draft_cfg, draft_tp, sds((B,), i32), cache8_tp,
                sds((B, max_pages), i32), sds((B,), i32), sds((B,), b1),
                K, 0.0, None, None, "gather", None, smesh,
            ).compile().as_text(),
        }
        assert set(tp_lowered) == set(budgets.TP_PROGRAMS)
        # per-SHARD pool shapes: H/tp heads per shard (head axis 1 of the
        # pools, axis 2 of the scale side buffers)
        shard_shapes = budgets.shard_pool_shapes(g)
        other_ops = tuple(o for o in COLLECTIVE_OPS if o != "all-reduce")
        for name in budgets.TP_PROGRAMS:
            hlo = tp_lowered[name]
            budget = budgets.tp_loop_all_reduce_budget(name, g)
            assert_no_while_body_collectives(hlo, ops=other_ops)
            ar = while_body_collectives(hlo, ops=("all-reduce",))
            n_ar = sum(len(ls) for ls in ar.values())
            report[f"{name}_loop_all_reduces"] = n_ar
            assert n_ar == budget, (
                f"{name}: {n_ar} in-loop all-reduces, budget {budget} "
                "(two megatron activation collectives per layer per step)"
            )
            report[f"{name}_loop_pool_copies"] = sum(
                n
                for shape in shard_shapes
                for n in _assert_pool_aliases(hlo, shape, f"the {name} loops").values()
            )

        # GQA under tp (AUDIT_GQA_TP: 4 query heads, 2 KV heads, tp=2 —
        # one KV head, i.e. one whole query GROUP, per shard). The claim
        # docs/SERVING.md "Attention variants" makes: grouping shrinks the
        # per-shard pool BYTES by the group factor while the in-loop
        # all-reduce count stays exactly the megatron budget — the same
        # 2 * n_layer the MHA tp_decode program pays, not one op more.
        gtp = budgets.AUDIT_GQA_TP
        mc_gtp = GPTConfig(
            block_size=gtp.block_size,
            vocab_size=gtp.vocab_size,
            n_layer=gtp.n_layer,
            n_head=gtp.n_head,
            n_embd=gtp.n_embd,
            n_kv_heads=gtp.n_kv_heads,
        )
        params_gtp_abs = jax.eval_shape(
            lambda k: GPT.init(mc_gtp, k), jax.random.PRNGKey(0)
        )
        cache_gtp_abs = jax.eval_shape(
            lambda: PagedKVCache.init(
                mc_gtp, num_pages=gtp.num_pages, page_size=gtp.page_size,
                dtype=jnp.float32,
            )
        )
        params_gtp = _shard_abs(
            params_gtp_abs, serve_param_specs(params_gtp_abs, smesh)
        )
        cache_gtp = _shard_abs(cache_gtp_abs, serve_cache_specs(cache_gtp_abs))
        gqa_tp_hlo = _serve_decode_chunk.lower(
            mc_gtp, params_gtp, sds((B,), i32), cache_gtp,
            sds((B, max_pages), i32), sds((B,), i32), sds((B,), b1),
            g.decode_chunk, 0.0, None, None, "gather", None, smesh, 1,
        ).compile().as_text()
        assert_no_while_body_collectives(gqa_tp_hlo, ops=other_ops)
        ar = while_body_collectives(gqa_tp_hlo, ops=("all-reduce",))
        n_ar = sum(len(ls) for ls in ar.values())
        report["tp_decode_gqa_loop_all_reduces"] = n_ar
        gqa_budget = budgets.tp_loop_all_reduce_budget("tp_decode_gqa", gtp)
        assert n_ar == gqa_budget, (
            f"tp_decode_gqa: {n_ar} in-loop all-reduces, budget {gqa_budget} "
            "— GQA must not change the megatron activation collective count"
        )
        gqa_shard_pool = budgets.pool_shape(gtp, "f32", gtp.tp)
        report["tp_decode_gqa_loop_pool_copies"] = sum(
            _assert_pool_aliases(
                gqa_tp_hlo, gqa_shard_pool, "the tp_decode_gqa loops"
            ).values()
        )
    return report
