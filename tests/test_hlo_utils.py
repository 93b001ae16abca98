"""Direct unit tests for the utils/hlo.py parser (previously exercised only
through the structural pins that consume it) and the analysis/hlo_audit.py
text-level audits built on top of it."""

import jax
import jax.numpy as jnp
import pytest

from midgpt_tpu.analysis.hlo_audit import (
    CompileCounter,
    assert_fp32_master_params,
    assert_no_while_body_collectives,
    entry_parameter_dtypes,
    fp32_master_param_audit,
    jit_cache_size,
    while_body_collectives,
)
from midgpt_tpu.utils.hlo import collective_census, hlo_computations, while_body_names

# Shaped like a post-optimization dump: layout annotations and a nested-brace
# constant inside instruction lines, an indented closing brace, and a while
# whose body computation calls a fusion holding an all-gather.
SAMPLE_HLO = """\
HloModule test, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %c = f32[2,2]{1,0} constant({ {1, 2}, {3, 4} })
  ROOT %ag = f32[4]{0} all-gather(f32[4]{0} %param_0), replica_groups={}
  }

%region_0.22 (arg_tuple.23: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg_tuple.23 = (s32[], f32[4]{0}) parameter(0)
  %f = f32[4]{0} fusion(f32[4]{0} %gte), kind=kLoop, calls=%fused_computation
}

%region_2.47 (arg_tuple.48: (s32[], f32[4])) -> pred[] {
  %arg_tuple.48 = (s32[], f32[4]{0}) parameter(0)
}

ENTRY %main.62 (Arg_0.1: f32[4], Arg_1.2: bf16[4], Arg_2.3: s32[]) -> f32[4] {
  %w = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t), condition=%region_2.47, body=%region_0.22
}
"""


def test_hlo_computations_parses_bodies_and_nested_braces():
    comps = hlo_computations(SAMPLE_HLO)
    assert set(comps) == {"fused_computation", "region_0.22", "region_2.47", "main.62"}
    # the nested-brace constant is ONE instruction line, not a scope change
    assert any("constant({ {1, 2}, {3, 4} })" in l for l in comps["fused_computation"])
    assert len(comps["region_0.22"]) == 2
    # indented closing brace (fused_computation) still closed the scope
    assert all("parameter(0)" not in l for l in comps["region_2.47"][1:])


def test_hlo_computations_malformed_missing_close():
    """A header met while a computation is still open (truncated/malformed
    dump) starts the new computation instead of glomming instructions."""
    txt = (
        "%a (x: f32[]) -> f32[] {\n"
        "  %i1 = f32[] parameter(0)\n"
        "%b (y: f32[]) -> f32[] {\n"
        "  %i2 = f32[] parameter(0)\n"
        "}\n"
    )
    comps = hlo_computations(txt)
    assert [l for l in comps["a"]] == ["%i1 = f32[] parameter(0)"]
    assert [l for l in comps["b"]] == ["%i2 = f32[] parameter(0)"]


def test_hlo_computations_header_without_brace_is_not_a_computation():
    txt = "%notacomp (x: f32[])\n%real (y: f32[]) -> f32[] {\n  %i = f32[] parameter(0)\n}\n"
    comps = hlo_computations(txt)
    assert set(comps) == {"real"}


def test_while_body_names_and_census():
    assert while_body_names(SAMPLE_HLO) == {"region_0.22"}
    census = while_body_collectives(SAMPLE_HLO)
    # transitive: the all-gather hides inside a fusion the body calls
    assert [l for l in census["region_0.22"] if "all-gather" in l]
    with pytest.raises(AssertionError, match="all-gather"):
        assert_no_while_body_collectives(SAMPLE_HLO)
    assert_no_while_body_collectives(SAMPLE_HLO, ops=("all-to-all",))


def test_collective_census_reads_op_dtype_and_size_as_the_v5e_spells_them():
    """Lines copied from the two FSDP step programs compiled for a v5e 2x2:
    the authored reduce-scatter keeps its jax primitive's name, a tuple-typed
    all-reduce counts as its largest member, async starts count, and ops that
    merely consume a collective's result do not."""
    txt = """
  %reduce_scatter.93 = bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)} reduce-scatter(%get-tuple-element.2215), channel_id=1, dimensions={1}
  %all-reduce.42 = bf16[6144,2048]{1,0:T(8,128)(2,1)} all-reduce(%input.5), channel_id=73, to_apply=%add.1.clone
  %all-reduce.35 = (f32[]{:T(128)}, f32[]{:T(128)}) all-reduce(%add.533, %multiply_reduce_fusion.10), channel_id=70
  %all-to-all.60 = bf16[4,2,1024,2048]{2,3,1,0:T(8,128)(2,1)S(1)} all-to-all(%copy.611), channel_id=12, dimensions={0}
  %all-gather-start.1 = (bf16[3,2048,512]{2,1,0}, bf16[3,2048,2048]{2,1,0}) all-gather-start(%p.1), dimensions={2}
  %fusion.7 = bf16[2048,2048]{1,0} fusion(%reduce_scatter.93), kind=kLoop, calls=%fused_computation.7
"""
    assert collective_census(txt) == [
        ("reduce-scatter", "bf16", 2048 * 2048),
        ("all-reduce", "bf16", 6144 * 2048),
        ("all-reduce", "f32", 1),
        ("all-to-all", "bf16", 4 * 2 * 1024 * 2048),
        ("all-gather", "bf16", 3 * 2048 * 2048),
    ]


_PERMUTE_HLO = """\
HloModule permutes

%fused_dot (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  ROOT %convolution.1 = bf16[8,8]{1,0} convolution(%p0, %p0), dim_labels=bf_io->bf
}

%loop_body (arg: (bf16[8,8])) -> (bf16[8,8]) {
  %arg = (bf16[8,8]{1,0}) parameter(0)
  %x = bf16[8,8]{1,0} get-tuple-element(%arg), index=0
  %collective-permute-start.1 = (bf16[8,8]{1,0:T(8,128)(2,1)}, bf16[8,8]{1,0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%x), channel_id=1, source_target_pairs={{0,1},{1,0}}
  %collective-permute-start.2 = (f32[8,8]{1,0}, f32[8,8]{1,0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%x), channel_id=2, source_target_pairs={{0,1},{1,0}}
  %collective-permute-done.2 = f32[8,8]{1,0} collective-permute-done(%collective-permute-start.2)
  %fusion.7 = bf16[8,8]{1,0} fusion(%x), kind=kOutput, calls=%fused_dot
  %collective-permute-done.1 = bf16[8,8]{1,0} collective-permute-done(%collective-permute-start.1)
  ROOT %t = (bf16[8,8]{1,0}) tuple(%collective-permute-done.1)
}

ENTRY %main (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8]{1,0} parameter(0)
  %init = (bf16[8,8]{1,0}) tuple(%a)
  %w = (bf16[8,8]{1,0}) while(%init), condition=%cond, body=%loop_body
  ROOT %out = bf16[8,8]{1,0} get-tuple-element(%w), index=0
}
"""


# `gather` instructions as the v5e's compiler printed them in the XL decode
# program of PR 57's parent (the first) and of PR 57 (the next three), and one
# whose only mark is the frame it was traced in.
_FRAME_TABLES = """\
FileNames
1 "/root/repo/midgpt_tpu/ops/rope.py"

FunctionNames
1 "apply_rope_positions"
2 "rotate_interleaved_strided"

FileLocations
1 {file_name_id=1 function_name_id=1 line=96 end_line=96 column=10 end_column=42}
2 {file_name_id=1 function_name_id=2 line=34 end_line=34 column=9 end_column=20}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}

"""
ROTARY_GATHER_LINES = {
    "channels_of_an_activation": (1, '  %gather.126 = bf16[16,16,64]{2,1,0:T(8,128)(2,1)} gather(%param_0.1157, %transpose.186), offset_dims={0,1}, collapsed_slice_dims={2}, start_index_map={2}, index_vector_dim=1, slice_sizes={16,16,1}, indices_are_sorted=true, metadata={op_name="jit(_serve_decode_chunk)/while/body/closed_call/gather" stack_frame_id=1}'),
    "table_rows_by_position": (0, '  %gather.35 = bf16[16,128]{1,0:T(8,128)(2,1)} gather(%param_0.1161, %transpose.100), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,128}, metadata={op_name="gather" stack_frame_id=1}'),
    "embedding_rows": (0, '  %gather.33 = bf16[16,2048]{1,0:T(8,128)(2,1)} gather(%param_0.1144, %transpose.96), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,2048}, metadata={op_name="jit(_serve_decode_chunk)/while/body/closed_call/jit(_take)/gather" stack_frame_id=1}'),
    "one_element_by_a_full_index": (0, '  %gather.36 = s32[16]{0:T(128)} gather(%param_0.1170, %custom-call.16), offset_dims={}, collapsed_slice_dims={0,1}, start_index_map={0,1}, index_vector_dim=1, slice_sizes={1,1}, metadata={op_name="jit(_serve_decode_chunk)/while/body/closed_call/jit(take_along_axis)/gather" stack_frame_id=1}'),
    "traced_in_rotate_interleaved": (1, '  %gather.9 = bf16[16,128]{1,0} gather(%p, %i), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,128}, metadata={op_name="gather" stack_frame_id=2}'),
}


@pytest.mark.parametrize("case", list(ROTARY_GATHER_LINES))
def test_rotary_gathers_counts_channel_picks_and_not_rows_by_position(case):
    from midgpt_tpu.analysis.hlo_audit import rotary_gathers

    want, line = ROTARY_GATHER_LINES[case]
    assert rotary_gathers(_FRAME_TABLES + line + "\n") == want
    assert rotary_gathers(line + "\n") == (want if case != "traced_in_rotate_interleaved" else 0)


# Instructions of the XL decode program's while body as the v5e's compiler
# printed them (AOT compile, PR 62; operands and metadata cut): what
# `weight_copies` counts and what it must not. Each is read inside a
# computation that is not fused, beside a fusion whose own computation holds
# a weight-shaped `slice` that must not count a second time.
_L, _D = 24, 2048
WEIGHT_COPY_LINES = {
    # the parent of PR 62: a multi-output `slice` fusion of five layers' wqkv, `/*index=5*/` marks and all
    "multi_output_slice_fusion_of_five_layers": (1, "  %fusion.2020 = (bf16[1,3,2048,2048]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[1,3,2048,2048]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[1,3,2048,2048]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[1,3,2048,2048]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[1,3,2048,2048]{3,2,1,0:T(8,128)(2,1)S(1)}, /*index=5*/bf16[1,3,2048,2048]{3,2,1,0:T(8,128)(2,1)S(1)}) fusion(%get-tuple-element.695), kind=kLoop, calls=%fused_computation.289"),
    "one_layer_sliced_in_step": (1, "  %slice.4482 = bf16[1,3,2048,2048]{3,2,1,0:T(8,128)(2,1)S(1)} slice(%bitcast.77), slice={[5:6], [0:3], [0:2048], [0:2048]}"),
    "a_layer_flattened_to_two_dims": (1, "  %copy.12 = bf16[6144,2048]{1,0:T(8,128)(2,1)} copy(%bitcast.9)"),
    "a_layer_of_w_up_transposed_in_a_fusion": (1, "  %fusion.7 = bf16[1,8192,2048]{2,1,0:T(8,128)(2,1)} fusion(%p), kind=kLoop, calls=%fused_computation.3"),
    # the compiler's own prefetches, which run beside compute
    "asynchronous_slice_start": (0, "  %slice-start = ((bf16[24,8192,2048]{2,1,0:T(8,128)(2,1)}), bf16[1,8192,2048]{2,1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%get-tuple-element.612), slice={[3:4], [0:8192], [0:2048]}"),
    "asynchronous_slice_done": (0, "  %slice-done = bf16[1,8192,2048]{2,1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start)"),
    "asynchronous_copy_of_the_whole_stack": (0, "  %copy-start = (bf16[24,3,2048,2048]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[24,3,2048,2048]{3,2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%p.3)"),
    # names for what is there
    "the_stack_as_a_parameter": (0, "  %param.3 = bf16[24,3,2048,2048]{3,2,1,0:T(8,128)(2,1)} parameter(3)"),
    "an_element_of_the_fusion_s_tuple": (0, "  %get-tuple-element.584 = bf16[1,3,2048,2048]{3,2,1,0:T(8,128)(2,1)S(1)} get-tuple-element(%fusion.233), index=1"),
    "a_bitcast_of_a_layer": (0, "  %bitcast.5 = bf16[6144,2048]{1,0:T(8,128)(2,1)} bitcast(%get-tuple-element.584)"),
    "two_prefetched_buffers_joined_where_they_lie": (0, '  %custom-call.17 = bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)S(1)} custom-call(%slice-done, %slice-done.1), custom_call_target="ConcatBitcast"'),
    # as large as a layer and larger, and not a weight
    "the_pool_written_in_place": (0, "  %kv_write.3 = bf16[24,16,2049,8,128]{4,3,2,1,0:T(8,128)(2,1)} custom-call(%p.5, %k), custom_call_target=\"tpu_custom_call\""),
    "logits_of_128_slots": (0, "  %fusion.1181 = f32[128,50304]{1,0:T(8,128)} fusion(%x, %lm_head), kind=kOutput, calls=%fused_computation.9"),
    "a_prefill_call_s_activations": (0, "  %fusion.581 = bf16[16,16,6144]{2,1,0:T(8,128)(2,1)} fusion(%h, %w), kind=kOutput, calls=%fused_computation.11"),
}


@pytest.mark.parametrize("case", list(WEIGHT_COPY_LINES))
def test_weight_copies_counts_a_layer_written_out_again_and_not_its_names_or_prefetches(case):
    from midgpt_tpu.analysis.hlo_audit import weight_copies
    from midgpt_tpu.utils.hlo import hlo_instructions, result_bytes

    want, line = WEIGHT_COPY_LINES[case]
    text = (
        "HloModule serve\n\n%fused_computation.289 (p: bf16[24,3,2048,2048]) -> bf16[1,3,2048,2048] {\n"
        "  %p = bf16[24,3,2048,2048]{3,2,1,0} parameter(0)\n"
        "  ROOT %slice.1 = bf16[1,3,2048,2048]{3,2,1,0} slice(%p), slice={[5:6], [0:3], [0:2048], [0:2048]}\n}\n\n"
        # an asynchronous slice as an ATTACHED chip's compiler prints it (the 124M decode program, my chip run, PR 62,
        # call 4): the opcode is `async-start`, the `slice` sits in a computation of its own
        "%async_computation.4 (p.1: bf16[3,2048,2048]) -> bf16[1,2048,2048] {\n"
        "  %p.1 = bf16[3,2048,2048]{2,1,0} parameter(0)\n"
        "  ROOT %slice.9 = bf16[1,2048,2048]{2,1,0} slice(%p.1), slice={[0:1], [0:2048], [0:2048]}\n}\n\n"
        "%body (arg: (s32[])) -> (s32[]) {\n"
        "  %in_the_fusion = bf16[1,3,2048,2048]{3,2,1,0} fusion(%q), kind=kLoop, calls=%fused_computation.289\n"
        "  %slice-start.4 = ((bf16[3,2048,2048]{2,1,0:T(8,128)(2,1)}), bf16[1,2048,2048]{2,1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) async-start(%g), calls=%async_computation.4\n"
        "  %slice-done.4 = bf16[1,2048,2048]{2,1,0:T(8,128)(2,1)S(1)} async-done(%slice-start.4)\n"
        + line + "\n}\n"
    )
    # every leaf of `params.blocks`: the (L, C) scales are no matrices and are skipped
    shapes = [(_L, 3, _D, _D), (_L, _D, _D), (_L, 128), (_L, 4 * _D, _D), (_L, _D, 4 * _D)]
    assert weight_copies(text, shapes) == 1 + want  # `%in_the_fusion` is the 1: once, by its result
    assert weight_copies(text, [(_L, 3, 768, 768)]) == 0  # another model's widths
    if case == "multi_output_slice_fusion_of_five_layers":
        ((name, opcode, members),) = hlo_instructions([line.strip()])
        assert (name, opcode, len(members)) == ("fusion.2020", "fusion", 6)
        assert result_bytes(members) == 6 * 3 * _D * _D * 2
        assert result_bytes([("pred", (8,)), ("s32", ()), ("f8e4m3fn", (4, 4))]) == 8 + 4 + 16


def test_permute_overlap_census_reads_what_stands_between_start_and_done():
    """A start/done pair with a matmul fusion between them is covered, one
    with nothing between is not; dtypes come from the start's first buffer;
    a computation without permutes has no entry."""
    from midgpt_tpu.utils.hlo import permute_overlap_census

    assert permute_overlap_census(_PERMUTE_HLO) == [
        {"computation": "loop_body", "loop_body": True, "kind": "backward", "pairs": 2,
         "covered": 1, "dtypes": ["bf16", "f32"]}
    ]
    assert permute_overlap_census(SAMPLE_HLO) == []


def test_entry_parameter_dtypes_and_fp32_audit():
    assert entry_parameter_dtypes(SAMPLE_HLO) == ["f32", "bf16", "s32"]
    audit = fp32_master_param_audit(SAMPLE_HLO)
    assert audit == {"n_params": 3, "n_f32": 1, "n_reduced": 1, "has_bf16_compute": 1}
    with pytest.raises(AssertionError, match="fp32"):
        assert_fp32_master_params(SAMPLE_HLO)
    with pytest.raises(ValueError, match="ENTRY"):
        entry_parameter_dtypes("HloModule empty\n")


def test_parser_roundtrip_on_real_lowering():
    """End-to-end sanity on an actual compiled scan: the while body exists,
    parses, and is collective-free on one device."""

    @jax.jit
    def f(x):
        def body(c, _):
            return c * 1.5 + 1.0, None

        c, _ = jax.lax.scan(body, x, None, length=4)
        return c

    txt = f.lower(jnp.ones((8,), jnp.float32)).compile().as_text()
    comps = hlo_computations(txt)
    bodies = while_body_names(txt)
    assert bodies and bodies <= set(comps)
    assert_no_while_body_collectives(txt)
    assert entry_parameter_dtypes(txt) == ["f32"]


def test_compile_counter_and_cache_size():
    f = jax.jit(lambda x: x * 3 + 2)
    assert jit_cache_size(f) == 0
    with CompileCounter() as cc:
        f(jnp.ones((5, 3)))
    assert cc.count >= 1
    assert jit_cache_size(f) == 1
    with CompileCounter() as cc2:
        f(jnp.zeros((5, 3)))  # same shape/dtype: cache hit
    assert cc2.count == 0
    assert jit_cache_size(f) == 1
    with CompileCounter() as cc3:
        f(jnp.ones((2, 9)))  # new shape: recompile
    assert cc3.count >= 1
    assert jit_cache_size(f) == 2
