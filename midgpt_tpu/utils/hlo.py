"""Compiled-HLO introspection helpers shared by tests and tools.

Used by the structural pins that keep scheduling claims honest:
tests/test_shard_map_fsdp.py (gather/compute dataflow independence),
tests/test_configs_compile.py (at-scale configs lower), and
tests/test_chip_compile.py (the chip compiler's async collectives and what
its schedule puts between their starts and dones). One
parser and one abstract-lowering scaffold so the pins can't drift apart.
"""

from __future__ import annotations

import math
import re
import typing as tp


_HLO_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")


def hlo_computations(txt: str) -> tp.Dict[str, tp.List[str]]:
    """Parse post-optimization HLO text into {computation: instruction lines}.

    Computation headers look like `%name (args) -> type {` (ENTRY-prefixed
    for main, `%` optional across jax/XLA versions); instructions are the
    lines until the closing `}` (tolerated indented). A header encountered
    while a computation is still open — a malformed dump missing its closing
    brace — starts the new computation rather than silently glomming its
    instructions onto the previous one; braces *inside* instruction lines
    (layout annotations `{1,0}`, nested constant literals `{ {1,2} }`,
    metadata) never open or close a computation. Edge cases pinned by
    tests/test_hlo_utils.py.
    """
    comps: tp.Dict[str, tp.List[str]] = {}
    name = None
    for raw in txt.splitlines():
        line = raw.strip()
        m = _HLO_HEADER_RE.match(line)
        if m and line.endswith("{"):
            name = m.group(1)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def while_body_names(txt: str) -> tp.Set[str]:
    """Names of computations used as a while-loop body (``body=%name``)."""
    return set(re.findall(r"body=%([\w.\-]+)", txt))


# jax renamed the shard_map trace scopes: modern HLO metadata reads
# `jvp()/shard_map/...`, older releases `jvp(jit(shmap_body))/...`. Every
# structural pin matches through these helpers so the spelling difference
# can't silently turn a pin vacuous.


def in_shard_map_scope(line: str) -> bool:
    """Is this HLO instruction annotated as coming from a shard_map body?"""
    return "/shard_map/" in line or "shmap_body)" in line


def is_forward_shmap_line(line: str) -> bool:
    """Forward (jvp, not transpose(jvp)) shard_map provenance."""
    return in_shard_map_scope(line) and "jvp(" in line and "transpose(" not in line


def is_forward_body(lines: tp.Sequence[str]) -> bool:
    """Forward (jvp) vs backward (transpose(jvp)) scan-body classification,
    shared by tests/test_shard_map_fsdp.py and `gather_overlap_census` so
    the two overlap pins can't drift on what they call 'forward'."""
    return any(is_forward_shmap_line(l) and "while" in l for l in lines)


def gather_overlap_census(txt: str) -> tp.List[tp.Dict[str, tp.Any]]:
    """One entry per gather-bearing shard_map scan body of compiled TPU HLO:
    `{"body", "kind", "plain", "annotated", "fused"}`. The TPU compiler does
    not split async gathers into `all-gather-start`/`-done` pairs in its
    text; overlap shows as gathers ANNOTATED `async_collective_name=
    "all-gather-start*"` (the split happens in the backend scheduler) or as
    collective-continuation fusions (`calls=%async_collective_fusion.*`: a
    compute kernel carries the next layer's gather windows, the strongest
    form). A body whose gathers are all `plain` streams its weights behind
    compute. Bodies are found structurally (`body=%name` of a while op), not
    by metadata: leaf fusions inherit the body's op_name and must not be
    graded as bodies."""

    def is_async(l: str) -> bool:
        return (
            "all-gather-start(" in l
            or 'async_collective_name="all-gather-start' in l
        )

    bodies = while_body_names(txt)
    census = []
    for name, lines in hlo_computations(txt).items():
        if name not in bodies or not any("shard_map/while" in l for l in lines):
            continue
        annotated = sum(1 for l in lines if is_async(l))
        plain = sum(1 for l in lines if " all-gather(" in l and not is_async(l))
        fused = sum(1 for l in lines if "calls=%async_collective_fusion" in l)
        if plain + annotated + fused == 0:
            continue  # gather-free body (not a ZeRO-3 layer scan)
        census.append(
            {
                "body": name,
                "kind": "forward" if is_forward_body(lines) else "backward",
                "plain": plain,
                "annotated": annotated,
                "fused": fused,
            }
        )
    return census


_INSTRUCTION_RE = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = ")
_PERMUTE_START_RE = re.compile(r"= \(([a-z]+\d*)\[[\d,]*\][^ ]* .*? collective-permute-start\(")
_PERMUTE_DONE_RE = re.compile(r" collective-permute-done\(%?([\w.\-]+)\)")
_FUSION_CALLS_RE = re.compile(r" fusion\(.*calls=%([\w.\-]+)")


def permute_overlap_census(txt: str) -> tp.List[tp.Dict[str, tp.Any]]:
    """One entry per computation of compiled TPU HLO that holds asynchronous
    collective-permutes: `{"computation", "loop_body", "kind", "pairs",
    "covered", "dtypes"}`. The TPU compiler DOES split these in its text
    (`collective-permute-start` ... `collective-permute-done`), and the text
    is the schedule, so what a transfer runs beside is what stands between
    its start and its done: `covered` counts the pairs with at least one
    matmul fusion (a fusion whose computation holds a `convolution`) or
    Mosaic kernel (`tpu_custom_call`) there, `pairs` all of them. A pair
    with nothing between holds the chip as a synchronous collective would.
    `kind` is 'backward' where the computation is the backward of a
    shard_map scan (see `is_forward_body`), 'forward' otherwise."""
    comps = hlo_computations(txt)
    heavy_comps = {n for n, ls in comps.items() if any(" convolution(" in l for l in ls)}
    bodies = while_body_names(txt)
    census = []
    for name, lines in comps.items():
        heavy, starts, pairs, dtypes = [], {}, [], set()
        for i, line in enumerate(lines):
            m = _INSTRUCTION_RE.match(line)
            if m is None:
                continue
            called = _FUSION_CALLS_RE.search(line)
            if (called and called.group(1) in heavy_comps) or "tpu_custom_call" in line:
                heavy.append(i)
            start = _PERMUTE_START_RE.search(line)
            if start:
                starts[m.group(1)] = i
                dtypes.add(start.group(1))
            done = _PERMUTE_DONE_RE.search(line)
            if done and done.group(1) in starts:
                pairs.append((starts[done.group(1)], i))
        if pairs:
            census.append(
                {
                    "computation": name,
                    "loop_body": name in bodies,
                    "kind": "forward" if is_forward_body(lines) else "backward",
                    "pairs": len(pairs),
                    "covered": sum(any(a < h < b for h in heavy) for a, b in pairs),
                    "dtypes": sorted(dtypes),
                }
            )
    return census


_COLLECTIVE_RE = re.compile(
    r"= (\(?[a-z]+\d*\[.*?) "
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)(?:-start)?\("
)
_ARRAY_TYPE_RE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")


def collective_census(txt: str) -> tp.List[tp.Tuple[str, str, int]]:
    """(op, dtype, elements) of every cross-device collective instruction in
    compiled HLO text; a tuple-typed collective counts as its largest member.
    What tells the two FSDP schedules apart on the chip's compiler
    (tests/test_chip_compile.py): the authored one reduce-scatters weight-
    sized gradients and holds no all-to-all; the compiler's all-reduces them
    and moves activations through all-to-alls. The dtype is the precision the
    cross-chip gradient sum is carried in."""
    census = []
    for line in txt.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            continue
        members = [
            (dtype, math.prod(int(d) for d in dims.split(",") if d))
            for dtype, dims in _ARRAY_TYPE_RE.findall(m.group(1))
        ]
        dtype, elements = max(members, key=lambda de: de[1])
        census.append((m.group(2), dtype, elements))
    return census


def jit_cache_size(fn: tp.Any) -> tp.Optional[int]:
    """Compiled-program count in a jit wrapper's cache (None if the jax
    version does not expose it). One entry per distinct (static args,
    input avals) combination that actually lowered + compiled."""
    probe = getattr(fn, "_cache_size", None)
    return probe() if callable(probe) else None


_RELAYOUT_RE = re.compile(
    r"= \(?[a-z]+[0-9]*\[([0-9,]*)\][^=\n]*? (?:copy|copy-start|transpose)\("
)


def pool_relayouts(
    hlo_text: str, pool_shapes: tp.Iterable[tp.Sequence[int]]
) -> int:
    """Copies and transposes in a compiled program whose result is as large
    as a KV-pool buffer or ONE LAYER of it: the TPU layout census of the
    serving programs (PagedKVCache "Layout contract").

    `pool_shapes` are the logical shapes of the pool's leaves ((L, H, P,
    ps, C) pools, (L, P, H, ps) int8 scale buffers); a result counts when
    its dims are a leaf's, or a leaf's without its layer dim (with or
    without a unit dim in its place), in any dtype and layout. Every
    computation of the module is read, so a relayout fused into a `fusion`
    is counted by the `copy`/`transpose` inside it; an entry PARAMETER is a
    parameter, not a copy, and is not counted. What it finds, on the chip's
    compiler: a program that scatters into the pool relays it out on entry
    and on exit (2 per pool tensor) and copies one layer per tensor per
    layer for the attention custom call, 4 + 2L for a bf16 pool (PR 25);
    one that keeps the contract reads 0. On other backends the number is
    whatever that backend's lowering does and pins nothing."""
    wanted = set()
    for shape in pool_shapes:
        dims = tuple(int(d) for d in shape)
        wanted |= {dims, dims[1:], (1,) + dims[1:]}
    return sum(
        1
        for m in _RELAYOUT_RE.finditer(hlo_text)
        if tuple(int(d) for d in m.group(1).split(",") if d) in wanted
    )


_GATHER_RE = re.compile(
    r" gather\([^\n]*?collapsed_slice_dims=\{([0-9,]*)\}[^\n]*?slice_sizes=\{([0-9,]*)\}"
    r"(?:[^\n]*?stack_frame_id=(\d+))?"
)


def _frame_functions(hlo_text: str) -> tp.Dict[str, str]:
    """{stack_frame_id: the function its innermost frame lies in}, from the
    FunctionNames / FileLocations / StackFrames tables a compiled text
    carries ahead of its computations (empty where it carries none)."""
    functions = hlo_text.partition("\nFunctionNames\n")[2].partition("\n\n")[0]
    names = dict(re.findall(r'^(\d+) "([^"\n]*)"$', functions, re.M))
    location = dict(re.findall(r"^(\d+) \{file_name_id=\d+ function_name_id=(\d+) ", hlo_text, re.M))
    frames = re.findall(r"^(\d+) \{file_location_id=(\d+) ", hlo_text, re.M)
    return {frame: names.get(location.get(loc, ""), "") for frame, loc in frames}


def rotary_gathers(hlo_text: str) -> int:
    """`gather` instructions of a compiled program that pick CHANNELS out of
    an activation: the census of the interleaved rotary's lowering
    (ops/rope.py; PERF.md section 6 PR 57).

    A gather counts when it collapses its operand's MINOR dim and takes
    another dim whole (the stride-2 channel slices `x[..., ::2]` of
    `rotate_interleaved_strided` came out of the chip's compiler as
    `bf16[16,16,64] gather(bf16[16,16,128], ...)`, `collapsed_slice_dims={2}`,
    `slice_sizes={16,16,1}`: four a layer, with their layout copies 3.8 ms of
    the XL's 12.7 ms prefill call), or when its stack frame lies in a function
    whose name starts with `rotate_interleaved`. NOT counted: rows taken whole
    by an index (the embedding's and the rotary tables' rows by position,
    `collapsed_slice_dims={0}`: they stay, `jnp.take(table, positions)`), and
    an element picked by a full index (`take_along_axis` of a token id: every
    dim collapsed). A program whose rotation rolls lanes reads 0."""
    where = _frame_functions(hlo_text)

    def counts(collapsed: str, sizes: str, frame: str) -> bool:
        sizes = [int(d) for d in sizes.split(",") if d]
        picks_channels = str(len(sizes) - 1) in collapsed.split(",") and max(sizes) > 1
        return picks_channels or where.get(frame, "").startswith("rotate_interleaved")

    return sum(counts(*m) for m in _GATHER_RE.findall(hlo_text))


_RESULT_RE = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\(")


def hlo_instructions(lines: tp.Iterable[str]) -> tp.Iterator[tp.Tuple[str, str, tp.List[tp.Tuple[str, tp.Tuple[int, ...]]]]]:
    """(name, opcode, [(dtype, dims) of each array of its result]) of the
    instruction lines of one computation (`hlo_computations`). A tuple-typed
    result gives one entry per member; the `/*index=5*/` marks a long tuple
    type carries are dropped before it is read."""
    for line in lines:
        m = _RESULT_RE.match(re.sub(r"/\*.*?\*/", "", line))
        if m is None:
            continue
        members = [
            (dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims in _ARRAY_TYPE_RE.findall(m.group(2))
        ]
        yield m.group(1), m.group(3), members


def result_bytes(members: tp.Iterable[tp.Tuple[str, tp.Sequence[int]]]) -> int:
    """Bytes an instruction writes: the sum over its result's arrays
    (`hlo_instructions`), the padding of the device's tiling not counted. An
    element type carries its width in bits (`bf16`, `s32`, `f8e4m3fn`);
    `pred` is a byte."""
    def bits(dtype: str) -> int:
        m = re.match(r"[a-z]+(\d+)", dtype)
        return int(m.group(1)) if m else 8

    return sum(math.prod(dims) * bits(dtype) // 8 for dtype, dims in members)


# Instructions that move no weight (names for what is there, control flow; a
# custom call is a Mosaic kernel, or the compiler's `ConcatBitcast` that joins
# two prefetched buffers where they lie), and the prefetches the compiler
# places itself and overlaps with compute.
_WRITES_NOTHING = frozenset({
    "parameter", "get-tuple-element", "tuple", "bitcast", "constant", "while",
    "conditional", "call", "optimization-barrier", "custom-call",
})
_ASYNC_PREFETCH = frozenset({
    "slice-start", "slice-done", "copy-start", "copy-done",
    # as an attached chip's compiler prints them: `%slice-start.4 = ... async-start(...), calls=%async_computation.4`
    "async-start", "async-update", "async-done",
})
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")


def weight_copies(
    hlo_text: str, weight_shapes: tp.Iterable[tp.Sequence[int]]
) -> int:
    """Instructions of a compiled program that WRITE a layer of a stacked
    weight matrix out again: the census of how the serving programs' matmuls
    reach their weights (GPT._decode_layer_loop; PERF.md section 6 PR 62).

    `weight_shapes` are the stacked leaves' logical shapes, layers leading
    (`wqkv` (L, 3, D, D), `w_up` (L, 4D, D), ...: every leaf of a family's
    params may be handed over, as the serving engine's census does; a leaf of
    fewer than three dims is no stack of matrices, an embedding table or the
    norms' scales, and is skipped). Read are the computations
    that no instruction `calls=` (a fusion or an asynchronous wrapper counts
    once, by its own result, and not by the instructions inside its
    computation). An instruction counts when an array
    of its result has a layer's dims, unit dims aside, as they lie or
    flattened to two ((3, D, D) or (3D, D)), one layer or several stacked,
    in any dtype and layout; a multi-output fusion that writes 19 layers
    counts once. NOT counted: what moves nothing (`_WRITES_NOTHING`), and
    the asynchronous prefetches (`slice-start` / `slice-done` / `copy-start`
    / `copy-done`, printed with the opcode `async-start` / `async-done` by
    an attached chip's compiler) that the compiler places itself and
    overlaps with compute. What it found on the chip's compiler: the XL decode program that
    indexed the stack and then reshaped the layer flat (`wqkv[i].reshape(3D,
    D)`) wrote all 24 layers' `wqkv` in two `slice` fusions EVERY STEP, 604
    MB read and written again, 1.46 ms of a 6.7 ms step (PR 57's reading); a
    program whose projection contracts the `(3, D, D)` layer as it lies
    (since PR 62 the unrolled loop's own choice) reads 0. On other backends the number is whatever that lowering does and pins
    nothing."""
    forms = set()
    for shape in weight_shapes:
        if len(shape) < 3:
            continue
        layer = tuple(int(d) for d in shape[1:] if int(d) != 1)
        forms |= {layer, (math.prod(layer[:-1]), layer[-1])}
    comps = hlo_computations(hlo_text)
    called = set(_CALLS_RE.findall(hlo_text))

    def holds_a_layer(dims: tp.Tuple[int, ...]) -> bool:
        dims = tuple(d for d in dims if d != 1)
        return dims in forms or dims[1:] in forms

    return sum(
        1
        for name, lines in comps.items()
        if name not in called
        for _, opcode, members in hlo_instructions(lines)
        if opcode not in _WRITES_NOTHING
        and opcode not in _ASYNC_PREFETCH
        and any(holds_a_layer(dims) for _, dims in members)
    )


def lower_abstract_train_step(config, mesh=None, eval_program=False):
    """Lower the full training step against ABSTRACT sharded inputs — or,
    with `eval_program`, the batched eval the train loop runs beside it
    (`eval_loss_many` over a stacked (N, B, T) set: the same model under the
    same mesh, through the implicit-GSPMD forward whatever `fsdp_mode` is).

    No buffers are materialized, so this works for 7B-class configs on a
    CPU test host and for AOT device topologies (tests/test_chip_compile.py
    passes a mesh built from jax.experimental.topologies devices).
    Param/optimizer sharding specs follow the same rule selection as
    training/train.py init_state (pipeline rule under pp>1, else the
    Megatron-tp rule, which reduces to plain FSDP at tp=1).
    """
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.parallel.fsdp import named_shardings
    from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
    from midgpt_tpu.training.optim import make_optimizer
    from midgpt_tpu.training.train import make_train_step

    if mesh is None:
        mesh = make_mesh(config.mesh)
    mc = config.model_config
    optimizer, _ = make_optimizer(config)

    if mesh.shape["pp"] > 1:
        from midgpt_tpu.parallel.pipeline import pipeline_param_specs as spec_rule
    else:
        from midgpt_tpu.parallel.tp import tp_param_specs

        spec_rule = functools.partial(tp_param_specs, vocab_parallel=config.tp_vocab)

    abstract_params = jax.eval_shape(
        lambda k: GPT.init(mc, k), jax.random.PRNGKey(0)
    )
    param_specs = spec_rule(
        abstract_params, mesh, config.shard_model, config.fsdp_min_size
    )
    params_abs = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=s),
        abstract_params,
        named_shardings(param_specs, mesh),
    )
    opt_abs = jax.eval_shape(optimizer.init, params_abs)
    opt_specs = spec_rule(opt_abs, mesh, config.shard_model, config.fsdp_min_size)
    opt_abs = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        opt_abs,
        named_shardings(opt_specs, mesh),
    )

    step, _, eval_loss_many = make_train_step(config, optimizer, mesh, param_specs)
    G, B, T = config.g_accum_iters, config.batch_size, mc.block_size
    data_sh = NamedSharding(mesh, batch_spec(shard_seq=mesh.shape["sp"] > 1))
    x_abs = jax.ShapeDtypeStruct((G, B, T), jnp.int32, sharding=data_sh)
    if eval_program:
        return eval_loss_many.lower(params_abs, x_abs, x_abs)
    key_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return step.lower(params_abs, opt_abs, x_abs, x_abs, key_abs)
