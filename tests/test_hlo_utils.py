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
