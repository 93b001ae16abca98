"""Declarative budget manifest for the compiled-artifact audits.

Every numeric pin the serving stack promises about its lowered programs —
how many in-loop collectives a tensor-parallel body may carry, how many
pool-sized copies a decode loop may make (zero), what geometry the audit
suite lowers against — lives HERE, once. Both consumers read this module:

  * `analysis/hlo_audit.py run_audit()` lowers the serving programs at
    `AUDIT` geometry and asserts each census against these budgets;
  * `tests/test_recompile_pins.py::test_audit_suite_passes_on_cpu_mesh`
    re-asserts the report keys against the SAME numbers.

A new serving mode declares its budget by adding one entry to
`TP_LOOP_LAYERS` (or one constant below); drift between the audit and the
pin tests is then structurally impossible — there is no second literal to
forget. No JAX import: the manifest must be loadable by the lint pass and
by the tests' collection phase without touching a backend.
"""

from __future__ import annotations

import dataclasses
import typing as tp


@dataclasses.dataclass(frozen=True)
class AuditGeometry:
    """The tiny abstract-lowering geometry the audit suite runs at.

    Small enough to lower in seconds on the 1-core CI host, large enough
    that every structural feature exists: >1 layer (so step-scan bodies
    carry a per-layer collective multiple), >1 head (so tp=2 sharding is
    head-aligned), a paged pool with more pages than any one request.
    """

    n_layer: int = 2
    n_head: int = 2
    n_embd: int = 32
    head_dim: int = 16  # n_embd // n_head
    block_size: int = 64
    vocab_size: int = 128
    num_pages: int = 9
    page_size: int = 8
    batch: int = 2
    max_pages: int = 8
    decode_chunk: int = 4
    spec_k: int = 2
    split_k: int = 4
    tp: int = 2
    draft_n_layer: int = 1
    # Attention-variant knobs (docs/SERVING.md "Attention variants"):
    # n_kv_heads = 0 means MHA (KV heads == query heads); a smaller value
    # shrinks the paged pool's head axis to the KV-head count, which is
    # exactly what the copy census must grep. Window/sinks change masking
    # only — pool geometry is untouched.
    n_kv_heads: int = 0
    sliding_window: int = 0
    attn_sinks: int = 0

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_head


AUDIT = AuditGeometry()

# Variant lowerings the audit suite must also hold the zero-copy /
# collective-free pins on: MQA (2 query heads sharing 1 KV head — the
# extreme grouping, so any head-fold bug in the lowering surfaces), the
# same MQA geometry with a sliding window + sinks (masking must not add
# pool traffic), and a GQA tensor-parallel geometry (4 query heads, 2 KV
# heads, tp=2: one KV head — one whole query GROUP — per shard).
AUDIT_GQA = AuditGeometry(n_kv_heads=1)
AUDIT_GQA_WINDOW = AuditGeometry(n_kv_heads=1, sliding_window=24, attn_sinks=8)
AUDIT_GQA_TP = AuditGeometry(n_head=4, head_dim=8, n_kv_heads=2)

# The megatron sharding contract (docs/SERVING.md "Mesh-sharded serving"):
# one activation all-reduce after the attention output projection and one
# after the MLP down projection — per layer, per decode step, and nothing
# else (zero all-gather / all-to-all / reduce-scatter / collective-permute
# in any serving loop body).
MEGATRON_ALL_REDUCES_PER_LAYER = 2

# How many transformer layers execute inside ONE while-body iteration of
# each tp-audited program. The step-scan programs (decode, int8 decode,
# split-K decode, int8 draft) unroll all their layers inside the body; the
# verify program is lowered with decode_layer_scan=True so its body IS a
# single layer. Values are AuditGeometry field names (resolved at query
# time) or plain ints.
TP_LOOP_LAYERS: tp.Dict[str, tp.Union[str, int]] = {
    "tp_decode": "n_layer",
    "tp_decode_int8": "n_layer",
    "tp_decode_split": "n_layer",  # split-K must not move the budget
    "tp_verify": 1,  # layer-scan body = one layer = one megatron pair
    "tp_draft_int8": "draft_n_layer",
    # GQA must not move the budget either: grouping shrinks pool BYTES per
    # shard, never the megatron activation all-reduce count (lowered at
    # AUDIT_GQA_TP geometry, hence outside TP_PROGRAMS' shared-shape loop)
    "tp_decode_gqa": "n_layer",
}

TP_PROGRAMS: tp.Tuple[str, ...] = tuple(
    k for k in TP_LOOP_LAYERS if k != "tp_decode_gqa"
)

# Pool/scale copy budget inside ANY serving loop body, split or not,
# sharded or not: the KV pool aliases through the loop carry (the r5/r6
# perf pin), so the census must find exactly zero pool-sized copies beyond
# the per-scatter allowance below (hlo_audit.loop_pool_copy_excess).
LOOP_POOL_COPY_BUDGET = 0

# What ONE in-loop pool scatter may cost on the lowering the audit runs on.
# XLA's CPU backend (jaxlib 0.9) canonicalizes a scatter by transposing its
# operand so the scattered dims lead, copies the result back into the loop
# carry, and the fused gather read transposes it back again: up to three
# pool-shaped `copy` instructions per scatter, whatever the carry structure
# (the older CPU lowering this census was first pinned on emitted none).
# The census exists for the failure where the CARRY re-materializes the
# pool — the r1-r4 structure (cache as scan xs + stacked ys) has no scatter
# at all, so it gets no allowance and still fails. The chip's lowering is
# the one that matters and has never been censused: ROADMAP S4.
LOOP_POOL_COPIES_PER_SCATTER = 3

# Report keys that pin an all-zero copy census for the split-K lowerings
# (dict-per-while-body form: every value must be 0).
SPLIT_ZERO_COPY_KEYS: tp.Tuple[str, ...] = (
    "split_decode_loop_pool_copies",
    "split_verify_loop_pool_copies",
    "split_decode_int8_loop_pool_copies",
    "split_decode_int8_loop_scale_copies",
)

# The split-K decode body census is also collective-free; the report key
# holds {body: n_collectives} and every value must be 0.
SPLIT_ZERO_COLLECTIVE_KEYS: tp.Tuple[str, ...] = ("split_decode_while_bodies",)

# Round-overlap dispatch (docs/SERVING.md "Round-overlap dispatch"): the
# fused multi-round group program (`_serve_decode_group`) wraps k decode
# rounds in one lax.scan, so its while body carries the ENTIRE pool through
# the scan carry. The aliasing pin must hold at every audited round_group —
# a single in-loop pool copy would multiply by k rounds per dispatch and
# erase the overlap win. `run_audit` lowers the group program at these
# round_group values (f32 at both, int8 at the first).
ROUND_GROUPS_AUDITED: tp.Tuple[int, ...] = (2, 4)

# All-zero copy census keys for the group lowerings (same dict-per-body
# form as the split-K keys above: every value must be 0).
GROUP_ZERO_COPY_KEYS: tp.Tuple[str, ...] = (
    "group2_decode_loop_pool_copies",
    "group4_decode_loop_pool_copies",
    "group2_decode_int8_loop_pool_copies",
    "group2_decode_int8_loop_scale_copies",
)

# The group scan body is single-engine work — zero collectives of any kind
# may appear in it ({body: n_collectives}, every value 0).
GROUP_ZERO_COLLECTIVE_KEYS: tp.Tuple[str, ...] = (
    "group2_decode_while_bodies",
    "group4_decode_while_bodies",
)

# Attention-variant lowerings (docs/SERVING.md "Attention variants"): the
# KV-head-shrunk pool must STILL alias through every decode loop carry —
# grouping changes pool geometry, which is precisely the kind of change
# that silently breaks XLA's donation/aliasing match — and window masking
# must add zero pool traffic (it is select math on scores, not data
# movement). Same dict-per-body report form as the split/group keys.
VARIANT_ZERO_COPY_KEYS: tp.Tuple[str, ...] = (
    "gqa_decode_loop_pool_copies",
    "gqa_window_decode_loop_pool_copies",
    "gqa_decode_int8_loop_pool_copies",
    "gqa_decode_int8_loop_scale_copies",
)

VARIANT_ZERO_COLLECTIVE_KEYS: tp.Tuple[str, ...] = (
    "gqa_decode_while_bodies",
    "gqa_window_decode_while_bodies",
)


def tp_loop_all_reduce_budget(
    program: str, geom: AuditGeometry = AUDIT
) -> int:
    """In-loop all-reduce budget for one tp-audited serving program."""
    layers = TP_LOOP_LAYERS[program]
    if isinstance(layers, str):
        layers = getattr(geom, layers)
    return MEGATRON_ALL_REDUCES_PER_LAYER * layers


def tp_mesh_shape(geom: AuditGeometry = AUDIT) -> tp.Dict[str, int]:
    """The serving mesh the tp audits lower against (pure tp, no data)."""
    return {"tp": geom.tp, "data": 1}


def pool_shape(
    geom: AuditGeometry = AUDIT, dtype: str = "f32", tp_shards: int = 1
) -> str:
    """HLO shape string of one KV pool buffer (the copy-census grep key).

    Layout [L, H_kv, P, ps, D] per models/gpt.py PagedKVCache — the head
    axis is the KV-head count (== n_head only for MHA; GQA geometries
    shrink it by the group factor). Under tensor parallelism that same
    axis shards, so the per-shard census greps kv_heads // tp_shards.
    """
    return (
        f"{dtype}[{geom.n_layer},{geom.kv_heads // tp_shards},"
        f"{geom.num_pages},{geom.page_size},{geom.head_dim}]"
    )


def scale_shape(
    geom: AuditGeometry = AUDIT, tp_shards: int = 1
) -> str:
    """HLO shape string of an int8 pool's f32 scale side buffer.

    Layout [L, P, H_kv, ps] (page-major so the per-page quantization
    scales gather alongside the page table; KV-head axis like the pools).
    """
    return (
        f"f32[{geom.n_layer},{geom.num_pages},"
        f"{geom.kv_heads // tp_shards},{geom.page_size}]"
    )


def shard_pool_shapes(
    geom: AuditGeometry = AUDIT,
) -> tp.Tuple[str, ...]:
    """All per-shard pool/scale shapes the tp copy census must grep."""
    return (
        pool_shape(geom, "f32", geom.tp),
        pool_shape(geom, "s8", geom.tp),
        scale_shape(geom, geom.tp),
    )
