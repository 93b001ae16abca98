"""Decoder-only GPT as a plain pytree + pure functions, TPU-first.

Architecture parity with the reference (for val-loss parity; see SURVEY.md §7):
  * pre-norm residual blocks with *weightless* RMSNorm (eps 1e-6 in blocks,
    1e-5 for the final norm — reference model.py:94-95,133)
  * fused QKV projection, QK-LayerNorm per head (learned scale, no bias,
    eps 1e-6 — reference model.py:52-53,64-65)
  * GPT-J interleaved rotary embeddings (reference layers.py:79-99)
  * bias-free Linears, truncated-normal(±2σ)/sqrt(fan_in) init (reference
    layers.py:49-50); embedding init N(0, 1/sqrt(D)) (reference model.py:134)
  * init-only weight tying: wte and lm_head start from the same array but are
    independent leaves that diverge from step 1 (reference model.py:135-138)
  * GELU MLP with 4x expansion (reference model.py:17-31)
  * fp32 softmax inside attention; logits returned in compute dtype and cast
    to fp32 by the loss (reference model.py:74-77, train.py:76)

TPU-first structure (different from the reference's Equinox modules):
  * Block parameters are stacked along a leading layer axis; the forward pass
    is ONE `jax.lax.scan` over that axis with `jax.checkpoint` per block
    (compile time O(1) in depth, remat bounds activation memory). The
    reference reaches the same shape via eqx.filter_vmap + filter scan
    (model.py:130-132,149-155); here it is the native representation.
  * The forward runs on a full (B, T) batch — batch semantics live in the
    model, not an outer vmap, so sharding constraints and Pallas kernels see
    the batched shapes they tile over.
  * Everything is shape-static and key-explicit: jit-safe by construction.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import typing as tp

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from midgpt_tpu.ops.attention import flash_or_blockwise, multihead_attention
from midgpt_tpu.ops.dropout import dropout
from midgpt_tpu.ops.norms import head_layer_norm, rms_norm
from midgpt_tpu.ops.quant import dequantize_q8, quantize_q8
from midgpt_tpu.ops.rope import apply_rope, apply_rope_bthc, rope_table
from midgpt_tpu.utils.pytree import pytree_dataclass

Array = jax.Array
KeyArray = jax.Array


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Model shape (mirrors reference model.py:108-115)."""

    block_size: int  # max sequence length
    vocab_size: int
    n_layer: int
    n_head: int
    n_embd: int
    dropout: float = 0.0
    # TPU knobs (not part of the reference config surface):
    # 'ring' / 'ulysses' = sequence parallelism over the mesh 'sp' axis
    # (parallel/ring_attention.py: K/V shards rotate by ppermute;
    # parallel/ulysses.py: one all-to-all trades the sequence sharding for a
    # head sharding and attention runs dense); the runtime injects the
    # mesh-bound implementation via the attn_fn hook on GPT.hidden.
    attn_impl: str = "naive"  # 'naive' | 'blockwise' | 'flash' | 'ring' | 'ulysses'
    # KV block of the blockwise/flash/ring/ulysses paths. At T <= this (1024
    # holds every GPT training config's sequence in one block) the flash
    # kernels are the tiled ones, whose score tile ops/attention.py
    # flash_block_sizes derives (256) and this field does not set; at T >
    # this it is the KV block of the multi-block kernels, and the ring's
    # per-pair tile. `train_124m` and `train_xl_fsdp4` (ledger) run 1024.
    attn_block_size: int = 1024
    remat: bool = True  # checkpoint each block inside the layer scan
    # What the per-block checkpoint may keep instead of recomputing in bwd:
    #   'none'  — save nothing (full recompute; minimum memory)
    #   'dots'  — save outputs of matmuls with no batch dims (the QKV/out/MLP
    #             projections; attention internals still recompute — they're
    #             cheap under flash and their T×T buffers are what remat is
    #             protecting against)
    #   'flash' — 'dots' plus the flash kernel's residuals (rotated q/k/v,
    #             attention output and log-sum-exp): backward recomputes
    #             nothing of attention — no transposes, no RoPE/QK-norm
    #             replay, no forward-kernel re-run — at the cost of saving
    #             ~4 (B,T,D)-sized buffers per layer
    remat_policy: str = "dots"
    scan_unroll: int = 1  # unroll factor of the layer scan
    # QKV projection lowering of the (3, D, D) weight (see _project_qkv):
    # 'fused' = one (BT,D)x(D,3D) matmul over the layer reshaped flat (best
    # MXU shape; the default, and what TRAINING runs: its scan hands the
    # projection one layer);
    # 'split3' = batched per-third einsum over the layer as it lies: required
    # under tensor parallelism (selected by the training runtime when mesh
    # tp > 1). The SERVING programs do not read this field where their layer
    # loop is unrolled or their mesh has tp > 1: `_decode_layer_loop` takes
    # 'split3' there (the flat reshape of a layer indexed out of the stacked
    # parameters is a copy of it on the chip; PERF.md, PR 62).
    qkv_proj: str = "fused"
    # RoPE lowering. 'interleaved' computes the reference rotation directly
    # (reference layers.py:79-99). 'split' computes the SAME function via a
    # per-head permutation of the q/k projection rows applied in-graph
    # (checkpoints stay in reference convention) + the contiguous
    # rotate-half form — mathematically identical (QK^T is invariant under
    # a shared permutation of the C axis; pinned by test_rope/test_model),
    # and cheaper where the interleaved form gathers stride-2 channels, fwd
    # AND bwd: in training (the serving programs roll lanes since PR 57:
    # ops/rope.py). The 124M cells run 'split', the XL cells 'interleaved'
    # (ledger); ROADMAP D4 asks whether 'split' still earns its weight
    # permutation in serving. Per-run choice recorded in config.json, so
    # restores and sampling stay consistent.
    rope_style: str = "interleaved"
    # Internal activation layout of the attention fast paths (flash kernel /
    # injected ring/ulysses — both consume head-major):
    #   'seq'  — project to (B,T,H,C), transpose to the kernel and back
    #            (the r1-r4 structure).
    #   'head' — project DIRECTLY to (B,H,T,C) (einsum btd,xhcd->xbhtc),
    #            QK-norm + RoPE in head-major, kernel without transposes,
    #            and merge+output-projection as ONE contraction
    #            (bhtc,dhc->btd). Same math, same params, same checkpoints —
    #            only the einsum axis order changes; kills the per-layer
    #            head-transpose copies the profiler showed (~12% of the r5
    #            124M step was relayout copies; measured on an earlier
    #            toolchain, not re-measured).
    # The naive/blockwise reference paths always use 'seq'.
    attn_layout: str = "seq"
    # Mixture-of-experts MLP (MoEParams): 0 = dense (reference semantics);
    # E > 0 replaces every block's MLP with E experts, top-k routed.
    n_experts: int = 0
    moe_top_k: int = 2
    # Decode-time layer loop lowering (decode_step / decode_step_paged):
    #   False — Python-unrolled DUS chain: the KV cache aliases through the
    #           decode loop carry with ZERO full-cache copies per token
    #           (the r5 restructure, pinned by test_sampling.py), but the
    #           decode program size and trace/compile time grow linearly
    #           with n_layer — fine at 12 layers, noticeably slower to
    #           compile per chunk length at the 32-layer 7B shapes.
    #   True  — rolled lax.scan over layers: O(1) compile in depth, at the
    #           measured cost of 2 full-cache copies per decode step at the
    #           inner/outer carry boundary (measured on an earlier toolchain,
    #           not re-measured). The deep
    #           llama7b configs set this.
    decode_layer_scan: bool = False
    # Grouped-query attention (GQA/MQA): number of K/V heads. None = MHA
    # (n_kv_heads == n_head, the reference layout — params, checkpoints and
    # compiled programs are byte-identical to the pre-GQA repo). Set to a
    # divisor of n_head to share each K/V head across n_head / n_kv_heads
    # query heads (query head h reads K/V head h // group); 1 = MQA. Every
    # KV buffer in the repo — dense KVCache, paged pools + int8 scale side
    # buffers, trie/spill entries — shrinks to (.., n_kv_heads, ..) geometry,
    # which is THE slots-per-HBM-byte lever (stacks with int8's 2x).
    n_kv_heads: tp.Optional[int] = None
    # Sliding-window attention: each query attends to its last
    # `sliding_window` keys (plus the first `attn_sinks` sink tokens —
    # StreamingLLM-style attention sinks, PAPERS.md). 0 = full causal
    # attention. A row with `count` visible keys attends to columns
    # [count - sliding_window, count) ∪ [0, min(attn_sinks, count)).
    # Training support: attn_impl 'naive' or 'blockwise' (the flash/ring/
    # ulysses kernels have no window mask — validated below). Serving:
    # every paged path masks by the same rule, and the engine reclaims
    # pages that fall fully behind the window (sampling/pages.py).
    sliding_window: int = 0
    attn_sinks: int = 0

    def __post_init__(self):
        if self.n_kv_heads is not None:
            if self.n_kv_heads < 1 or self.n_head % self.n_kv_heads:
                raise ValueError(
                    f"n_kv_heads={self.n_kv_heads} must be a positive divisor "
                    f"of n_head={self.n_head} (each K/V head serves a whole "
                    "group of query heads)"
                )
        if self.sliding_window != 0 and not (
            0 < self.sliding_window < self.block_size
        ):
            raise ValueError(
                f"sliding_window={self.sliding_window} must be 0 (full "
                f"attention) or in [1, block_size={self.block_size})"
            )
        if self.attn_sinks < 0:
            raise ValueError(f"attn_sinks={self.attn_sinks} must be >= 0")
        if self.attn_sinks > 0 and self.sliding_window == 0:
            raise ValueError(
                "attn_sinks > 0 requires sliding_window > 0 (sinks are the "
                "always-visible prefix OF a windowed mask; full attention "
                "already sees them)"
            )
        if self.sliding_window > 0:
            if self.attn_sinks + self.sliding_window > self.block_size:
                raise ValueError(
                    f"attn_sinks + sliding_window = "
                    f"{self.attn_sinks + self.sliding_window} exceeds "
                    f"block_size={self.block_size}"
                )
            if self.attn_impl not in ("naive", "blockwise"):
                raise ValueError(
                    f"sliding_window requires attn_impl 'naive' or "
                    f"'blockwise' (got {self.attn_impl!r}: the flash/ring/"
                    "ulysses training kernels carry no window mask)"
                )

    # -- what the runtime reads of any model config (models/__init__.py) --
    def model(self):
        return GPT

    def check_experiment(self, config) -> None:
        config.check_gpt_family()

    def check_serving(self, who: str) -> None:
        """sample.py and ServeEngine serve this family."""

    def check_training(self, who: str) -> None:
        """launch.py trains this family."""

    @property
    def head_dim(self) -> int:
        assert self.n_embd % self.n_head == 0
        return self.n_embd // self.n_head

    @property
    def kv_heads(self) -> int:
        """Number of K/V heads (n_head unless GQA/MQA is on)."""
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_head

    @property
    def kv_groups(self) -> int:
        """Query heads per K/V head (1 = MHA)."""
        return self.n_head // self.kv_heads


@pytree_dataclass
class AttentionParams:
    # (3, D, D) fused QKV projection with an explicit leading q/k/v axis.
    # This layout holds two properties at once that flat (3D, D) layouts
    # each break:
    #   * at tp=1 it reshapes (contiguous: free where the projection is
    #     handed ONE layer, as training's scan does; a copy of the layer where
    #     it was indexed out of the stack, GPTConfig.qkv_proj) to the flat
    #     stacked (3D, D) for ONE full-width matmul + contiguous split
    #     (a head-major interleaved flat layout's (B,T,H,3,C) unpack slices
    #     leave 64-element lane runs at C=64; `train_124m`, ledger, runs
    #     this one);
    #   * Megatron TP shards axis 1 (output features, parallel/tp.py): each
    #     of q, k, v is column-sharded independently, so shard boundaries
    #     land between whole heads (D = H*C head-major) and the schedule is
    #     collective-free between the column- and row-parallel matmuls —
    #     sharding a flat stacked 3D axis would straddle the q/k/v
    #     boundaries. (The two lowerings: GPTConfig.qkv_proj.)
    # Shape-distinct from both flat layouts, so a checkpoint from either
    # fails loudly at restore instead of silently permuting rows.
    # The reference's flat stacked split (reference model.py:63-66) is a row
    # permutation of this; init rows are iid so the distribution is
    # identical.
    wqkv: Array
    wo: Array  # (D, D) output projection
    q_scale: Array  # (C,) QK-LayerNorm scale for queries
    k_scale: Array  # (C,) QK-LayerNorm scale for keys
    # GQA/MQA (config.n_kv_heads set): the K/V projection moves to its own
    # (2, n_kv_heads * C, D) leaf — k then v along the leading axis — and
    # wqkv shrinks to the (1, D, D) query projection. Separate leaves keep
    # both Megatron column shards clean at DIFFERENT head counts: wqkv's
    # output axis splits by whole query heads, wkv's by whole K/V heads
    # (parallel/tp.py; requires n_kv_heads % tp == 0). None for MHA — the
    # leaf vanishes from the pytree, so MHA params, checkpoints and
    # compiled programs are byte-identical to the pre-GQA repo, and a GQA
    # checkpoint fails loudly (missing/extra leaf) against an MHA config
    # instead of silently permuting rows.
    wkv: tp.Optional[Array] = None


@pytree_dataclass
class MLPParams:
    w_up: Array  # (4D, D)
    w_down: Array  # (D, 4D)


@pytree_dataclass
class MoEParams:
    """Top-k routed MLP (n_experts > 0) — beyond the reference's capability
    set (its MLP is dense only, reference model.py:17-31). Expert weights
    carry a leading E axis that shards over the mesh 'ep' axis
    (parallel/tp.py): each ep shard computes ITS experts for all tokens and
    the combine contraction psums over E — expert-sharded compute with no
    token dispatch (the right EP schedule for the masked-dense lowering
    below; an all-to-all token-dispatch form is the large-E upgrade path).
    At n_experts=1 the routed MLP is exactly the dense MLP (gate softmax
    over one expert is 1.0) — parity pinned by tests/test_moe.py."""

    router: Array  # (E, D) — token -> expert logits, x @ router.T
    experts_up: Array  # (E, 4D, D)
    experts_down: Array  # (E, D, 4D)


@pytree_dataclass
class BlockParams:
    attn: AttentionParams
    mlp: tp.Union[MLPParams, MoEParams]  # MoEParams iff config.n_experts > 0
    # Block RMSNorms are weightless (reference model.py:94-95): no leaves.


@pytree_dataclass
class GPTParams:
    wte: Array  # (V, D) token embedding
    blocks: BlockParams  # every leaf stacked with leading (n_layer,) axis
    lm_head: Array  # (V, D), applied as x @ lm_head.T; init-tied to wte


@pytree_dataclass
class KVCache:
    """Static-shape decode cache: (L, B, H, S, C) keys/values, filled up to
    `length`. The reference has no KV cache at all — its generate loop runs a
    full padded forward per token (reference sample.py:72-94); this is the
    named upgrade in BASELINE.json."""

    k: Array  # (n_layer, B, n_kv_heads, S, head_dim)
    v: Array  # (n_layer, B, n_kv_heads, S, head_dim)
    length: Array  # () int32: number of valid positions

    @staticmethod
    def init(config: "GPTConfig", batch_size: int, dtype=jnp.bfloat16) -> "KVCache":
        shape = (
            config.n_layer,
            batch_size,
            config.kv_heads,
            config.block_size,
            config.head_dim,
        )
        return KVCache(
            k=jnp.zeros(shape, dtype),
            v=jnp.zeros(shape, dtype),
            length=jnp.zeros((), jnp.int32),
        )


# What the serving engine sizes and frees by: a kind of paged cache a family's
# layers need (`cache_kinds`, models/__init__.py). `window` 0: a page lives as
# long as its request; > 0: a page is dead once every future query's window
# has passed it. `sinks`: leading tokens that stay visible, never freed.
CacheKind = collections.namedtuple("CacheKind", "name window sinks")
# A kind of cache whose memory is a STATE a slot and not a row a token (a
# recurrent layer's: models/olmo_hybrid.py): `shapes(cache_dtype)` gives
# ((shape, dtype), ...) of ONE slot's row. The pool owner sizes it by the slot
# count, hands slot i row i and keeps its books (sampling/pages.py "State
# kinds"); the family's prefill begins a prompt's first chunk from zeros,
# whatever the row holds. It has no pages, no window and no table.
StateKind = collections.namedtuple("StateKind", "name shapes")


@pytree_dataclass
class PagedKVCache:
    """Paged decode cache for the continuous-batching serving engine.

    K/V live in a shared pool of fixed-size pages, (n_layer, n_kv_heads,
    num_pages, page_size, head_dim) per tensor — the head axis is the K/V
    head count, so GQA/MQA configs shrink every page (and its int8 scale
    rows) by the group factor, which is what turns the grouping into pages
    per HBM byte — and a request occupies
    whatever pages the host-side allocator (sampling/pages.py PageAllocator)
    hands it — so device memory holds O(sum of used lengths) instead of
    `n_slots * block_size` (the KVCache sizing above). Page 0 is the SINK:
    never allocated, it is what unallocated page-table entries (zeros) point
    at, so inactive/short slots READ it — always masked — while writes from
    inactive slots and pad positions are dropped via out-of-range page
    indices (XLA oob-scatter semantics; decode_step_paged /
    prefill_paged_chunk).

    The page table ((n_slots, max_pages) int32) and per-slot lengths are NOT
    part of this pytree: they are host-managed scheduler state passed into
    each serve step, so one compiled program serves any request mix — only
    the pool rides the jit carry (donated, updated in place; the
    no-full-cache-copies pin in tests/test_sampling.py covers it).

    page_size must be a multiple of 8 and head_dim a multiple of 128 — or
    span the full dim — for the Mosaic decode kernel's BlockSpec tiling
    (kernels/decode_attention.py); the XLA gather fallback has no such
    constraint.

    **Int8 storage mode** (dtype=jnp.int8): K/V pages are stored int8 with
    f32 absmax scales in small side buffers `k_scale`/`v_scale` of shape
    (n_layer, num_pages, n_kv_heads, page_size) — one scale per written K/V
    vector per head (ops/quant.py: a page fills incrementally through the
    scatter write paths, so scale granularity cannot be coarser than a
    position without requantizing already-written columns). The layout
    puts (n_head, page_size) last so the decode kernel's per-page scale
    block (1, n_head, page_size) spans both trailing dims — Mosaic-tiling
    clean with no in-kernel transpose. Decode-attention HBM traffic halves
    vs bf16 and pages-per-byte doubles; the side buffers add 4/head_dim
    (~3% at C=128) on top. Rollback interacts exactly like the pools:
    freeing a page orphans its scale entries too, and they are rewritten
    before they are next read (the write-before-read invariant,
    docs/SERVING.md). In bf16 mode both scale fields are None.

    **Layout contract (TPU kernel path).** On the kernel path the pool
    has ONE DEVICE LAYOUT from a serving program's parameter to its
    result: row-major over (L, H, P, ps, C), the layout in which the
    paged-attention template copies a page at a time. Three rules keep it
    so, and a program that breaks one pays a whole-pool relayout per call
    (utils/hlo.py pool_relayouts counts them; PERF.md, PR 25):

      1. the pool's DEFAULT device layout is that layout. The TPU
         compiler gives a buffer whose trailing dim is under 128 lanes a
         compact layout with the PAGE dim minor, which no paged access can
         use, and a jit's parameters and results have the default layout.
         So `init(kernel_layout=True)` (the engine passes whether its
         resolved paged impl is 'kernel') allocates the channel dim at
         `pool_lanes(head_dim)`: `head_dim` rounded up to 128. With
         `page_size` a multiple of 8 the trailing (ps, lanes) dims are
         whole (8, 128) tiles and the default IS row-major. The lanes past
         `head_dim` are zero and stay zero; the kernels pad q and the
         written rows to the pool's lanes and drop them from the output.
         These are the bytes the kernel read before too (a row-major bf16
         block of 64 channels is lane-padded to 128), then from a copy.
         (Pinning a layout through jax's Format API instead keeps the
         shape but does not survive the persistent compile cache on jax
         0.9.0: PERF.md §6, PR 25.)
      2. only the in-place Pallas write (kernels/paged_write.py) writes
         it: an XLA scatter on the pool makes layout assignment give
         every pool-carrying value the scatter's preferred layout;
      3. nothing slices a layer out of it for a custom call: the kernels
         take the whole pool and a layer index (attention_template.py),
         and a prefill that gathers in XLA there carries the layer in its
         indices (`_gather_layer_kv`).

    Off the kernel path (CPU, tests) the channel dim is `head_dim` and the
    XLA scatter and gather lowerings apply. Host-side users of pages
    (prefix cache, spill tier, page hand-off, resize) index (L, H, P) only
    and move whole pages, whatever their lanes; two engines that exchange
    pages must resolve the same paged impl. The int8 scale side buffers
    keep their (L, P, H, ps) shape, whose default layout is page-minor: a
    kernel-path int8 program relays the two of them (1/32 of the pool's
    bytes at C=128) out on entry and exit (PERF.md §7)."""

    # (n_layer, n_kv_heads, num_pages, page_size, head_dim), or
    # pool_lanes(head_dim) channels on the kernel path ("Layout contract")
    k: Array
    v: Array
    # int8 mode only: f32 absmax scales, (n_layer, num_pages, n_kv_heads,
    # page_size); None in bf16 mode (the leaves simply vanish from the
    # pytree, so bf16 programs are byte-identical to the pre-int8 repo).
    k_scale: tp.Optional[Array] = None
    v_scale: tp.Optional[Array] = None

    @staticmethod
    def init(
        config: "GPTConfig",
        num_pages: int,
        page_size: int = 8,
        dtype=jnp.bfloat16,
        kernel_layout: bool = False,
    ) -> "PagedKVCache":
        """A zeroed pool; `kernel_layout` allocates the channel dim at
        `pool_lanes(head_dim)` (docstring, "Layout contract")."""
        lanes = pool_lanes(config.head_dim) if kernel_layout else config.head_dim
        shape = (config.n_layer, config.kv_heads, num_pages, page_size, lanes)
        if jnp.dtype(dtype) == jnp.int8:
            sshape = (config.n_layer, num_pages, config.kv_heads, page_size)
            return PagedKVCache(
                k=jnp.zeros(shape, jnp.int8),
                v=jnp.zeros(shape, jnp.int8),
                k_scale=jnp.zeros(sshape, jnp.float32),
                v_scale=jnp.zeros(sshape, jnp.float32),
            )
        return PagedKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))

    @staticmethod
    def page_bytes(
        config: "GPTConfig", page_size: int, dtype, kernel_layout: bool = False
    ) -> int:
        """K+V bytes of ONE page across all layers/heads — the unit the
        byte-budgeted pool sizing divides by (sampling/pages.py
        `pool_hbm_bytes`). Deliberately excludes the int8 scale side
        buffers: the budget governs the page pools (what doubles), and the
        +4/head_dim side buffer is reported separately via
        ServeEngine.cache_hbm_bytes() so drivers see the true spend.
        Uses the K/V head count: a GQA page is group-factor smaller, so a
        fixed byte budget admits group-factor more pages."""
        lanes = pool_lanes(config.head_dim) if kernel_layout else config.head_dim
        per_tok = config.n_layer * config.kv_heads * lanes
        return 2 * per_tok * page_size * jnp.dtype(dtype).itemsize

    def pool_arrays(self) -> tp.List[Array]:
        """The page pools (not the scale side buffers), for the layout census."""
        return [self.k, self.v]

    @property
    def quantized(self) -> bool:
        return self.k.dtype == jnp.int8

    @property
    def page_size(self) -> int:
        return self.k.shape[3]

    @property
    def num_pages(self) -> int:
        return self.k.shape[2]


def pool_lanes(head_dim: int) -> int:
    """The kernel-path pool's channel dim: `head_dim` rounded up to whole
    128-lane rows (PagedKVCache "Layout contract", rule 1)."""
    return -(-head_dim // 128) * 128


@pytree_dataclass
class ServeCache:
    """The serving memory of every served family beside the GPT (whose
    `PagedKVCache` above carries the int8 side buffers and is what the page
    movers know: ROADMAP D18). It flattens to pools, then state, then
    counters, and the serving programs donate it whole.

    `pools[i]`: the arrays of PAGED kind i of the family's `cache_kinds`, as
    many as the kind needs (K and V; one array where a row is a latent; a
    latent row and its index key), all under that kind's one page table, each
    (cache layers, pool heads, pages, page_size, lanes) with page 0 the sink.
    The layer axis is the family's, not `n_layer`; `lanes` is the array's
    width, at `pool_lanes` on the kernel path (PagedKVCache "Layout contract",
    rule 1). `state`: the arrays of a STATE kind, (layers, rows, ...), the
    last row the sink (sampling/pages.py "State kinds" owns the rows); ()
    without one. `counters`: what the family's steps sum on the device, in the
    family's order, read by its `serve_counters` when somebody asks."""

    pools: tp.Tuple[tp.Tuple[Array, ...], ...]
    state: tp.Tuple[Array, ...] = ()
    counters: tp.Tuple[Array, ...] = ()

    @staticmethod
    def zeros(family: str, widths, num_pages: tp.Sequence[int], page_size: int, dtype, kernel_layout: bool,
              counters: tp.Sequence[Array], state_kind: tp.Optional[StateKind] = None) -> "ServeCache":
        """Zeroed pools of `num_pages[i]` pages for paged kind i, `widths[i]`
        giving (cache layers, pool heads, width) of each of its arrays, and
        zeroed state arrays of `num_pages[len(widths)]` rows (the pool owner's
        count: the slots and the sink row) in `state_kind`'s shapes."""
        if jnp.dtype(dtype) == jnp.int8:
            raise NotImplementedError(f"{family}: no int8 pool (the quantised write and read are the GPT's `PagedKVCache` alone)")
        lanes = pool_lanes if kernel_layout else (lambda width: width)
        pools = tuple(tuple(jnp.zeros((layers, heads, pages, page_size, lanes(width)), dtype) for layers, heads, width in kind)
                      for kind, pages in zip(widths, num_pages))
        state = () if state_kind is None else tuple(
            jnp.zeros((shape[0], num_pages[len(widths)], *shape[1:]), d) for shape, d in state_kind.shapes(dtype))
        return ServeCache(pools=pools, state=state, counters=tuple(counters))

    def pool_arrays(self) -> tp.List[Array]:
        """Every paged array, in kind order (the layout census reads them)."""
        return [a for kind in self.pools for a in kind]

    @property
    def page_size(self) -> int:
        return self.pools[0][0].shape[3]

    @property
    def num_pages(self) -> int:
        return self.pools[0][0].shape[2]


def _paged_write(
    pools: tp.Tuple[Array, Array, tp.Optional[Array], tp.Optional[Array]],
    i: Array,  # () int — layer index
    write_pages: Array,  # (...,) int32 — physical page per written position
    offs: Array,  # (...,) int32 — in-page offset per written position
    k: Array,  # (..., H, C) — the K vectors to store
    v: Array,  # (..., H, C) — the V vectors
    impl: str,  # resolved paged impl: 'kernel' | 'gather'
    mesh=None,
) -> tp.Tuple[Array, Array, tp.Optional[Array], tp.Optional[Array]]:
    """ONE column write of K and V into the paged pool, quantizing iff the
    scale buffers are present — the single write path all three paged
    forwards share (decode_step_paged / prefill_paged_chunk /
    verify_step_paged), so the int8 and bf16 modes cannot drift
    structurally. `pools` is (k, v, k_scale, v_scale) as the layer loops
    carry it: k/v (L, H, P, ps, C), scales (L, P, H, ps) f32 or None.

    Two lowerings of the same assignment, chosen with the attention
    lowering (`resolve_paged_impl`: the backend, once):

      * 'kernel' (TPU) — the in-place Pallas write
        (kernels/paged_write.py), pool aliased to pool through the page
        blocks the attention kernel reads. NO XLA scatter may touch the
        pool on this path: the TPU compiler gives every pool-carrying
        value of a program the layout its scatter prefers, and the whole
        pool is then relaid out at each boundary with the kernel's layout
        (PagedKVCache "Layout contract"). Out-of-range pages are skipped
        explicitly.
      * 'gather' (CPU, tests) — the XLA advanced-indexing scatter, which
        lowers to an in-place aliasing scatter inside donated loop carries
        (i/write_pages/offs are the advanced indices, H and C ride as
        slices — the zero-in-loop-pool-copy pin, tests/test_sampling.py
        and tests/test_quant_cache.py). The scale scatter has the same
        advanced index tuple over its (L, P, H, ps) layout, so it aliases
        identically; out-of-range write_pages (inactive slots, pad
        positions) drop BOTH writes via XLA oob-scatter semantics.

    Both store the same values at the same positions, bit for bit
    (tests/test_paged_write.py).

    A pool that is ONE array (`pools[1]` and `v` None: a latent cache, whose
    row is stored once and whose V is a view of it) takes the same two
    lowerings with one tensor; no int8 form of it is wired."""
    ck, cv, cks, cvs = pools
    if cv is None:
        k = k.astype(ck.dtype)
        if impl == "kernel":
            from midgpt_tpu.kernels.paged_write import paged_write

            if ck.shape[-1] != k.shape[-1]:
                k = jnp.pad(k, [(0, 0)] * (k.ndim - 1) + [(0, ck.shape[-1] - k.shape[-1])])
            return paged_write(ck, None, i, write_pages.reshape(-1), offs.reshape(-1),
                               k.reshape(-1, *k.shape[offs.ndim:]), None, mesh=mesh)
        return ck.at[i, :, write_pages, offs, :].set(k), None, None, None
    quantized = cks is not None
    if quantized:
        k, ks = quantize_q8(k)  # (..., H, C) int8, (..., H) f32
        v, vs = quantize_q8(v)
    else:
        k, v, ks, vs = k.astype(ck.dtype), v.astype(cv.dtype), None, None
    if impl == "kernel":
        from midgpt_tpu.kernels.paged_write import paged_write

        # rows at the pool's lanes: zeros past head_dim (they stay zero);
        # K and V each at their own pool's (a family with K 192 / V 128)
        lanes = lambda a, pool: [(0, 0)] * (a.ndim - 1) + [(0, pool.shape[-1] - a.shape[-1])]
        if ck.shape[-1] != k.shape[-1]:
            k = jnp.pad(k, lanes(k, ck))
        if cv.shape[-1] != v.shape[-1]:
            v = jnp.pad(v, lanes(v, cv))
        rows = lambda a: None if a is None else a.reshape(-1, *a.shape[offs.ndim:])
        return paged_write(
            ck, cv, i, write_pages.reshape(-1), offs.reshape(-1),
            rows(k), rows(v), cks, cvs, rows(ks), rows(vs), mesh=mesh,
        )
    ck = ck.at[i, :, write_pages, offs, :].set(k)
    if quantized:
        cks = cks.at[i, write_pages, :, offs].set(ks)
    cv = cv.at[i, :, write_pages, offs, :].set(v)
    if quantized:
        cvs = cvs.at[i, write_pages, :, offs].set(vs)
    return ck, cv, cks, cvs


def _gather_layer_kv(
    pool: Array,  # (L, H, P, ps, C) — the whole K or V pool
    scales: tp.Optional[Array],  # (L, P, H, ps) f32 | None
    i: Array,  # () int — layer index
    page_rows: Array,  # (..., MP) int32 — each slot's logical->physical pages
    out_dtype,
    head_dim: int,  # C: the pool's lanes past it are padding
) -> Array:
    """Gather each slot's pages of layer i contiguous -> (..., H, MP*ps, C),
    dequantizing after the gather in int8 mode (the CPU sibling of the
    kernel's in-VMEM dequant). Used by a prefill that attends in XLA on a
    TPU (Ouro's, models/ouro.py; the GPT's went to the template in PR 54);
    the per-layer-pool variant lives in kernels/decode_attention.py. ONE gather
    whose indices carry the layer beside the page reads the pages from the
    pool where it lies: slicing the layer out first makes the TPU compiler
    materialise it, a layer-sized copy per tensor per layer (PR 25)."""
    _, H, _, ps, _ = pool.shape
    lead, MP, C = page_rows.shape[:-1], page_rows.shape[-1], head_dim
    g = pool[i, :, page_rows][..., :C]  # (..., MP, H, ps, C): advanced dims lead
    g = jnp.moveaxis(g, -3, -4).reshape(*lead, H, MP * ps, C)
    if scales is None:
        return g
    sg = scales[i, page_rows]  # (..., MP, H, ps)
    sg = jnp.moveaxis(sg, -2, -3).reshape(*lead, H, MP * ps)
    return dequantize_q8(g, sg).astype(out_dtype)


def _repeat_kv(config: "GPTConfig", a: Array, axis: int) -> Array:
    """Broadcast K/V heads to the query head count for GQA (no-op for MHA).

    Query head h reads K/V head h // kv_groups (consecutive grouping), so
    the repeat along the head axis places each K/V head's copies exactly at
    its group's query-head indices — the same convention the paged kernel
    template realizes as a free (B, H_q, R, C) -> (B, H_kv, G*R, C)
    reshape (kernels/attention_template.py)."""
    g = config.kv_groups
    return a if g == 1 else jnp.repeat(a, g, axis=axis)


def _remat_policy(name: str):
    if name == "none":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name == "dots_attn":
        # Projections AND the attention output: backward never re-runs the
        # flash forward kernel (attention is >half the block FLOPs at T=1024;
        # its own bwd already recomputes p from the saved lse).
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names("attn_out"),
        )
    if name == "flash":
        # Everything attention-shaped: rotated q/k/v (head-major, named in
        # block_apply), the kernel's output and log-sum-exp (named in its
        # fwd rule). Backward starts attention AD directly at the saved
        # kernel residuals.
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                "q_rot", "k_rot", "v_proj", "attn_out", "attn_lse"
            ),
        )
    raise ValueError(
        f"unknown remat_policy {name!r} "
        "(expected 'none', 'dots', 'dots_attn' or 'flash')"
    )


def _linear_init(key: KeyArray, out_features: int, in_features: int) -> Array:
    """Truncated-normal(±2σ) scaled 1/sqrt(fan_in) (reference layers.py:49-50)."""
    w = jax.random.truncated_normal(key, -2.0, 2.0, (out_features, in_features))
    return w / math.sqrt(in_features)


class GPT:
    """Namespace of pure functions over (GPTConfig, GPTParams)."""

    @staticmethod
    def init(config: GPTConfig, key: KeyArray) -> GPTParams:
        block_key, embed_key = jax.random.split(key)
        D, C = config.n_embd, config.head_dim

        def init_block(k: KeyArray) -> BlockParams:
            k_attn, k_proj, k_up, k_down = jax.random.split(k, 4)
            if config.n_kv_heads is None:
                attn = AttentionParams(
                    # iid rows: the (3, D, D) reshape of a (3D, D) init is
                    # the same distribution as the reference's flat fused
                    # projection
                    wqkv=_linear_init(k_attn, 3 * D, D).reshape(3, D, D),
                    wo=_linear_init(k_proj, D, D),
                    q_scale=jnp.ones((C,)),
                    k_scale=jnp.ones((C,)),
                )
            else:
                # GQA: q at full width, k/v at n_kv_heads * C each (iid rows
                # again — one init per projection, split keys).
                KVD = config.kv_heads * C
                k_q, k_kv = jax.random.split(k_attn)
                attn = AttentionParams(
                    wqkv=_linear_init(k_q, D, D).reshape(1, D, D),
                    wo=_linear_init(k_proj, D, D),
                    q_scale=jnp.ones((C,)),
                    k_scale=jnp.ones((C,)),
                    wkv=_linear_init(k_kv, 2 * KVD, D).reshape(2, KVD, D),
                )
            if config.n_experts > 0:
                E = config.n_experts
                k_router, k_up, k_down = jax.random.split(k_up, 3)
                up = jax.vmap(lambda kk: _linear_init(kk, 4 * D, D))(
                    jax.random.split(k_up, E)
                )
                down = jax.vmap(lambda kk: _linear_init(kk, D, 4 * D))(
                    jax.random.split(k_down, E)
                )
                mlp = MoEParams(
                    router=_linear_init(k_router, E, D),
                    experts_up=up,
                    experts_down=down,
                )
            else:
                mlp = MLPParams(
                    w_up=_linear_init(k_up, 4 * D, D),
                    w_down=_linear_init(k_down, D, 4 * D),
                )
            return BlockParams(attn=attn, mlp=mlp)

        blocks = jax.vmap(init_block)(jax.random.split(block_key, config.n_layer))
        embed = jax.random.normal(embed_key, (config.vocab_size, D)) / math.sqrt(D)
        # Init-only tying: same values, independent leaves (reference model.py:135-138).
        return GPTParams(wte=embed, blocks=blocks, lm_head=embed)

    @staticmethod
    def _qkv_weights(
        config: GPTConfig, block: BlockParams
    ) -> tp.Tuple[Array, tp.Optional[Array], Array, Array]:
        """(wqkv, wkv | None, q_scale, k_scale), rope_style-adjusted.

        For rope_style='split', conjugate by the per-head C permutation on
        the WEIGHT side (one (2,D,D)-sized gather per layer, ~µs) instead of
        on the (B,T,H,C) activations (the expensive side): q/k emerge with
        interleaved pair (2i, 2i+1) at (i, i+C/2), so RoPE can use
        contiguous rotate-half. QK-norm and QK^T are permutation-invariant;
        v/att/wo untouched. Stored weights stay in the reference convention
        — checkpoints need no migration. Under GQA the same permutation
        applies to the q rows of wqkv (per query head) and the k rows of
        wkv[0] (per K/V head); wkv[1] (v) is untouched."""
        wqkv, wkv = block.attn.wqkv, block.attn.wkv
        q_scale, k_scale = block.attn.q_scale, block.attn.k_scale
        if config.rope_style == "split":
            from midgpt_tpu.ops.rope import split_permutation

            D, H, C = config.n_embd, config.n_head, config.head_dim
            perm = split_permutation(C)
            if wkv is None:
                wqk = wqkv[:2].reshape(2, H, C, D)[:, :, perm, :].reshape(2, D, D)
                wqkv = jnp.concatenate((wqk, wqkv[2:]), axis=0)
            else:
                HK, KVD = config.kv_heads, config.kv_heads * C
                wqkv = wqkv.reshape(H, C, D)[:, perm, :].reshape(1, D, D)
                wk = wkv[:1].reshape(HK, C, D)[:, perm, :].reshape(1, KVD, D)
                wkv = jnp.concatenate((wk, wkv[1:]), axis=0)
            q_scale, k_scale = q_scale[perm], k_scale[perm]
        return wqkv, wkv, q_scale, k_scale

    @staticmethod
    def _project_qkv_bhtc(
        config: GPTConfig, block: BlockParams, h: Array
    ) -> tp.Tuple[Array, Array, Array]:
        """h (B, T, D) -> q (B, H, T, C), k, v (B, H_kv, T, C), after
        QK-LayerNorm (no RoPE) — the attn_layout='head' projection: the
        head split rides the projection einsum's output axes instead of a
        separate transpose copy. Same contraction, same params. K/V come
        out at the K/V head count; GQA callers broadcast them to the query
        head count (_repeat_kv) only where an equal-heads kernel needs it."""
        H, C = config.n_head, config.head_dim
        wqkv, wkv, q_scale, k_scale = GPT._qkv_weights(config, block)
        if wkv is None:
            w = wqkv.reshape(3, H, C, config.n_embd)
            qkv = jnp.einsum("btd,xhcd->xbhtc", h, w)
            q, k, v = qkv[0], qkv[1], qkv[2]
        else:
            HK = config.kv_heads
            q = jnp.einsum(
                "btd,hcd->bhtc", h, wqkv.reshape(H, C, config.n_embd)
            )
            kv = jnp.einsum(
                "btd,xhcd->xbhtc", h, wkv.reshape(2, HK, C, config.n_embd)
            )
            k, v = kv[0], kv[1]
        q = head_layer_norm(q, q_scale)
        k = head_layer_norm(k, k_scale)
        return q, k, v

    @staticmethod
    def _project_qkv(
        config: GPTConfig, block: BlockParams, h: Array
    ) -> tp.Tuple[Array, Array, Array]:
        """h (B, T, D) -> q (B, T, H, C), k, v (B, T, H_kv, C) after
        QK-LayerNorm (no RoPE).

        Sequence-major (B, T, H, C) is the layout the fused projection
        produces with a plain reshape; the flash kernel consumes it natively,
        so the training hot path never materializes a head transpose.

        Two lowerings of the same (3, D, D) weight (see AttentionParams and
        GPTConfig.qkv_proj):
          'fused'  — reshape the weight flat and run ONE (BT, D) x (D, 3D)
                     matmul; best MXU shape, the default, what training
                     runs. The reshape moves nothing where the caller hands
                     over ONE layer (the scan of GPT.hidden / GPT.prefill);
                     of a layer indexed out of the STACKED parameters it is
                     a copy of the layer on the chip.
          'split3' — batched per-third einsum over the (3, D, D) layer as
                     it lies: the weight reaches the matmul in place, also
                     out of the stack, so the serving programs' unrolled
                     layer loop takes it whatever the config says
                     (`_decode_layer_loop`, which alone makes that choice).
                     Under tensor parallelism the flat reshape would
                     besides mix the tp-sharded feature axis into the
                     merged 3D axis (a reshard); the batched form keeps
                     each third independently column-sharded, zero
                     collectives (training/train.py selects it when mesh
                     tp > 1, the serving loop under a tp > 1 mesh).

        GQA (config.n_kv_heads set, AttentionParams.wkv) keeps the same two
        lowerings: 'fused' concatenates the q and k/v weights (a copy of
        both, whoever calls) into ONE
        (D + 2*H_kv*C, D) matmul with a contiguous split; 'split3' runs the
        q einsum and the batched k/v einsum separately so each stays
        independently column-sharded at its own head count. K/V emerge at
        the K/V head count — paged writes store them as-is, equal-heads
        attention kernels get them via _repeat_kv."""
        B, T, D = h.shape
        H, C = config.n_head, config.head_dim
        wqkv, wkv, q_scale, k_scale = GPT._qkv_weights(config, block)
        if wkv is None:
            HK = H
            if config.qkv_proj == "split3":
                qkv = jnp.einsum("btd,xed->btxe", h, wqkv)  # (B, T, 3, D)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            else:
                qkv = jnp.einsum("btd,ed->bte", h, wqkv.reshape(3 * D, D))
                q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            HK = config.kv_heads
            KVD = HK * C
            if config.qkv_proj == "split3":
                q = jnp.einsum("btd,ed->bte", h, wqkv[0])
                kv = jnp.einsum("btd,xed->btxe", h, wkv)  # (B, T, 2, KVD)
                k, v = kv[:, :, 0], kv[:, :, 1]
            else:
                w = jnp.concatenate(
                    [wqkv.reshape(D, D), wkv.reshape(2 * KVD, D)], axis=0
                )
                qkv = jnp.einsum("btd,ed->bte", h, w)
                q, k, v = jnp.split(qkv, [D, D + KVD], axis=-1)
        q = head_layer_norm(q.reshape(B, T, H, C), q_scale)
        k = head_layer_norm(k.reshape(B, T, HK, C), k_scale)
        v = v.reshape(B, T, HK, C)
        return q, k, v

    @staticmethod
    def _attn_out_and_mlp(
        config: GPTConfig,
        block: BlockParams,
        x: Array,  # (B, T, D) residual stream
        att: Array,  # (B, T, H, C), or (B, H, T, C) when head_major
        *,
        k_resid: tp.Optional[KeyArray] = None,
        k_mlp: tp.Optional[KeyArray] = None,
        inference: bool = True,
        head_major: bool = False,
        return_moe_aux: bool = False,
    ) -> tp.Union[Array, tp.Tuple[Array, Array]]:
        """Shared tail of a block: merge heads, output proj, MLP, residuals.

        With return_moe_aux (routed MLP only), returns (out, aux) where aux
        is the block's scalar load-balance term (_moe_gates)."""
        if head_major:
            # Merge + output projection as ONE contraction: wo's input axis
            # decomposes as (H, C) in the merged order, so this equals
            # reshape-merge + btd,ed->bte without the transpose copy.
            H, C = config.n_head, config.head_dim
            att = jnp.einsum(
                "bhtc,ehc->bte", att, block.attn.wo.reshape(config.n_embd, H, C)
            )
        else:
            B, T, H, C = att.shape
            att = att.reshape(B, T, config.n_embd)
            att = jnp.einsum("btd,ed->bte", att, block.attn.wo)
        att = dropout(att, config.dropout, k_resid, inference)
        x = x + att
        h = rms_norm(x)
        aux = None
        if config.n_experts > 0:
            if return_moe_aux:
                h, aux = GPT._moe_mlp(config, block.mlp, h, return_aux=True)
            else:
                h = GPT._moe_mlp(config, block.mlp, h)
        else:
            h = jax.nn.gelu(jnp.einsum("btd,ed->bte", h, block.mlp.w_up))
            h = jnp.einsum("bte,de->btd", h, block.mlp.w_down)
        h = dropout(h, config.dropout, k_mlp, inference)
        out = x + h
        return (out, aux) if return_moe_aux else out

    @staticmethod
    def _moe_gates(
        config: GPTConfig, mlp: "MoEParams", h: Array
    ) -> tp.Tuple[Array, Array]:
        """Router -> (gates (B, T, E) in h.dtype, load-balance aux () f32).

        Top-k selection goes through `jax.lax.top_k` INDICES, not a
        `logits >= kth` threshold: threshold masking admits MORE than k
        experts on exact logit ties — in the degenerate all-equal-logits
        state (a zero or collapsed router) every expert passes and routing
        silently turns dense (ADVICE r5). The index scatter keeps exactly k
        per token always (ties broken by lowest expert index,
        deterministic); for tie-free logits the masked set is identical, so
        gates are unchanged. Pinned by tests/test_moe.py.

        aux is the Switch-style load-balance term (Switch Transformer
        eq. 4-6, PAPERS.md): E * sum_e P_e * f_e with P_e the mean FULL
        softmax prob of expert e over tokens and f_e the mean top-k
        assignment fraction (divided by k so sum_e f_e = 1). Balanced
        routing gives exactly 1.0; a collapsed router approaches E/k * k
        terms -> > 1. It is differentiable through P_e only (f_e is a hard
        count), which is what makes it push probability mass toward
        under-assigned experts. Dead code (freely eliminated) unless the
        caller requests it — training folds it in behind
        ExperimentConfig.moe_aux_coef."""
        E = config.n_experts
        K = min(config.moe_top_k, E)
        logits = jnp.einsum("btd,ed->bte", h, mlp.router).astype(jnp.float32)
        probs_full = jax.nn.softmax(logits, axis=-1)  # (B, T, E) f32
        if K < E:
            idx = jax.lax.top_k(logits, K)[1]  # (B, T, K)
            assign = jnp.any(
                jax.nn.one_hot(idx, E, dtype=jnp.bool_), axis=-2
            )  # (B, T, E): exactly K True per token
            logits = jnp.where(assign, logits, -jnp.inf)
        else:
            assign = jnp.ones(logits.shape, jnp.bool_)
        gates = jax.nn.softmax(logits, axis=-1).astype(h.dtype)
        mean_prob = jnp.mean(probs_full, axis=(0, 1))  # (E,)
        mean_assign = jnp.mean(assign.astype(jnp.float32), axis=(0, 1)) / K
        aux = E * jnp.sum(mean_prob * mean_assign)
        return gates, aux

    @staticmethod
    def _moe_mlp(
        config: GPTConfig,
        mlp: "MoEParams",
        h: Array,
        return_aux: bool = False,
    ) -> tp.Union[Array, tp.Tuple[Array, Array]]:
        """Top-k routed expert MLP, masked-dense lowering.

        out = sum_e gate_e(h) * down_e(gelu(up_e(h))) with gates from a
        top-k-masked softmax over router logits (fp32, like attention's
        softmax — selection semantics in _moe_gates). The gate folds into
        `up` (down_e is linear), so the only E-sized activation is the
        (B, T, E, 4D) up buffer — sharded over 'ep' along E when expert
        parallelism is on; the combine einsum's E contraction is the EP
        all-reduce GSPMD inserts. FLOPs are E/top_k x a dense MLP in this
        lowering (fine for the small-E regime; token-dispatch all-to-all is
        the large-E upgrade path). With return_aux, also returns the
        scalar load-balance term."""
        gates, aux = GPT._moe_gates(config, mlp, h)
        up = jax.nn.gelu(jnp.einsum("btd,efd->btef", h, mlp.experts_up))
        up = up * gates[..., None]
        out = jnp.einsum("btef,edf->btd", up, mlp.experts_down)
        return (out, aux) if return_aux else out

    @staticmethod
    def block_apply(
        config: GPTConfig,
        params: BlockParams,
        x: Array,  # (B, T, D)
        *,
        key: tp.Optional[KeyArray] = None,
        inference: bool = False,
        rope: tp.Optional[tp.Tuple[Array, Array]] = None,
        positions: tp.Optional[Array] = None,
        attn_fn: tp.Optional[tp.Callable[[Array, Array, Array], Array]] = None,
        return_moe_aux: bool = False,
    ) -> tp.Union[Array, tp.Tuple[Array, Array]]:
        C = config.head_dim
        if rope is None:
            rope = rope_table(C, x.shape[1])
        sin, cos = rope
        if key is not None:
            k_attn_drop, k_resid, k_mlp = jax.random.split(key, 3)
        else:
            k_attn_drop = k_resid = k_mlp = None

        with jax.named_scope("attn"):
            att, head_major = GPT._attention(
                config, params, x, sin, cos, positions, attn_fn,
                k_attn_drop, inference,
            )
        with jax.named_scope("mlp"):
            return GPT._attn_out_and_mlp(
                config, params, x, att, k_resid=k_resid, k_mlp=k_mlp,
                inference=inference, head_major=head_major,
                return_moe_aux=return_moe_aux,
            )

    @staticmethod
    def _call_flash(config, T: int, q: Array, k: Array, v: Array) -> Array:
        """Invoke the Pallas kernel on head-major (B,H,T,C) q/k/v, naming
        the post-rope tensors for the 'flash' remat policy: with q/k/v
        saved here and out/lse saved in the kernel's fwd rule, backward
        resumes attention AD from residuals instead of replaying
        transpose+RoPE+QK-norm+kernel. ONE definition for both attn_layout
        modes so their remat/block-size behavior cannot drift."""
        import importlib

        from midgpt_tpu.ops.attention import flash_block_sizes

        fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")
        bq, bk = flash_block_sizes(T, config.attn_block_size)
        q = checkpoint_name(q, "q_rot")
        k = checkpoint_name(k, "k_rot")
        v = checkpoint_name(v, "v_proj")
        return fa.flash_attention(q, k, v, bq, bk)

    @staticmethod
    def _attention(
        config, params, x, sin, cos, positions, attn_fn, k_attn_drop, inference
    ) -> tp.Tuple[Array, bool]:
        """QKV + RoPE + dispatched attention.

        Returns (att, head_major): (B, H, T, C) with head_major=True when
        the attn_layout='head' fast path ran, else (B, T, H, C) with False.
        The flag is static (a function of config + dispatch), so the caller
        branches at trace time."""
        from midgpt_tpu.ops.attention import flash_kernel_usable

        h = rms_norm(x)  # weightless, eps 1e-6
        flash_ok = (
            config.attn_impl == "flash"
            and (config.dropout == 0.0 or inference)  # kernel has no dropout;
            # the dispatcher below raises for flash+dropout (training)
            and flash_kernel_usable(x.shape[1], config.attn_block_size)
        )
        if config.attn_layout == "head" and (attn_fn is not None or flash_ok):
            # Head-major end to end: no transposes between projection,
            # kernel and merge (attn_layout docstring above).
            q, k, v = GPT._project_qkv_bhtc(config, params, h)  # (B,H,T,C)
            q = apply_rope(q, sin, cos, positions, style=config.rope_style)
            k = apply_rope(k, sin, cos, positions, style=config.rope_style)
            # GQA: the flash/injected kernels take equal head counts —
            # broadcast K/V heads to the query heads (post-RoPE, so the
            # rotation runs at the smaller K/V width).
            k = _repeat_kv(config, k, 1)
            v = _repeat_kv(config, v, 1)
            if attn_fn is not None:
                if config.dropout != 0.0 and not inference:
                    raise NotImplementedError(
                        f"injected attention (attn_impl={config.attn_impl!r}) "
                        "does not support attention-probability dropout; use "
                        "attn_impl='naive' or set dropout=0.0"
                    )
                att = checkpoint_name(attn_fn(q, k, v), "attn_out")
            else:
                att = GPT._call_flash(config, x.shape[1], q, k, v)
            return att, True

        q, k, v = GPT._project_qkv(config, params, h)  # (B, T, H, C)
        q = apply_rope_bthc(q, sin, cos, positions, style=config.rope_style)
        k = apply_rope_bthc(k, sin, cos, positions, style=config.rope_style)
        # GQA: broadcast K/V heads to the query head count for the
        # equal-heads training impls (post-RoPE: the rotation and QK-norm
        # already ran at the smaller K/V width).
        k = _repeat_kv(config, k, 2)
        v = _repeat_kv(config, v, 2)

        if attn_fn is not None:
            # Runtime-injected attention (e.g. mesh-bound ring attention for
            # sequence parallelism) — head-major like the kernels.
            if config.dropout != 0.0 and not inference:
                raise NotImplementedError(
                    f"injected attention (attn_impl={config.attn_impl!r}) does "
                    "not support attention-probability dropout; use "
                    "attn_impl='naive' or set dropout=0.0"
                )
            att = attn_fn(
                q.transpose(0, 2, 1, 3),
                k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
            )
            att = checkpoint_name(att, "attn_out").transpose(0, 2, 1, 3)
        elif flash_ok:
            att = GPT._call_flash(
                config,
                x.shape[1],
                q.transpose(0, 2, 1, 3),
                k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
            )
            att = att.transpose(0, 2, 1, 3)
        else:
            att = multihead_attention(
                q,
                k,
                v,
                impl=config.attn_impl,
                dropout_rate=config.dropout,
                key=k_attn_drop,
                inference=inference,
                block_size=config.attn_block_size,
                layout="bthc",
                sliding_window=config.sliding_window,
                attn_sinks=config.attn_sinks,
            )
            att = checkpoint_name(att, "attn_out")
        return att, False

    @staticmethod
    def hidden(
        config: GPTConfig,
        params: GPTParams,
        tokens: Array,  # (B, T) int
        *,
        key: tp.Optional[KeyArray] = None,
        inference: bool = False,
        layer_scan: tp.Optional[tp.Callable] = None,
        attn_fn: tp.Optional[tp.Callable[[Array, Array, Array], Array]] = None,
        positions: tp.Optional[Array] = None,
        rope_len: tp.Optional[int] = None,
        return_moe_aux: bool = False,
    ) -> tp.Union[Array, tp.Tuple[Array, Array]]:
        """Backbone forward -> final-normed hidden states (B, T, D).

        `return_moe_aux` (routed MLP configs only) additionally returns the
        MoE load-balance term averaged over layers — a () f32 scalar the
        training loss folds in as `moe_aux_coef * aux`
        (ExperimentConfig.moe_aux_coef). Off by default, so the aux
        computation is dead code in every other caller.

        `positions` (shape (T,), absolute) + `rope_len` (static table length
        covering the largest position) let a sequence-parallel caller run the
        backbone on a LOCAL sequence shard: tokens are pointwise in T except
        attention (replaced via attn_fn) and RoPE, which these two arguments
        make shard-aware (shard g passes positions g*Tl + arange(Tl)).

        `attn_fn` (optional) replaces the config-dispatched attention with a
        runtime-bound implementation — the sequence-parallel path passes the
        mesh-bound ring attention here (attention is the only op that mixes
        information across T; everything else is token-pointwise, so GSPMD
        keeps those ops sharded over 'sp' without collectives).

        The lm_head projection is applied by `apply` (full logits, inference)
        or fused into the chunked loss (training — ops/loss.py
        fused_linear_cross_entropy, which avoids the (B*T, V) f32 buffer).

        `layer_scan(block_fn, x, (blocks, layer_keys)) -> (x, ys)` replaces
        `jax.lax.scan` over the layer stack. The explicit-FSDP path
        (parallel/shard_map_fsdp.py exchange_behind_scan) passes its own here:
        the blocks arrive SHARDED, it gathers a layer's weights before
        `block_fn` (the block under the config's remat policy) and brings its
        own backward over the stack, in which the gathers are replayed
        (ZeRO-3 re-gather) and a layer's cross-chip gradient sum runs one
        layer behind."""
        B, T = tokens.shape
        C = config.head_dim
        if key is not None:
            drop_key, layers_key = jax.random.split(key)
            layer_keys = jax.random.split(layers_key, config.n_layer)
        else:
            drop_key, layer_keys = None, None

        # jax.named_scope boundaries (embed / block / attn / mlp / final_norm)
        # label the profiler trace like reference model.py:28,55,97,140 —
        # benchmarks/reduce.py groups device op times by them.
        with jax.named_scope("embed"):
            x = jnp.take(params.wte, tokens, axis=0)  # (B, T, D)
            x = dropout(x, config.dropout, drop_key, inference)

        # shared fp32 table, constant-folded under jit; rope_len covers the
        # global sequence when T is a local shard of it
        rope = rope_table(C, rope_len or T)

        if return_moe_aux and config.n_experts == 0:
            raise ValueError("return_moe_aux requires a routed MLP (n_experts > 0)")

        def block_fn(x, block_and_key):
            block, k = block_and_key
            with jax.named_scope("block"):
                out = GPT.block_apply(
                    config, block, x, key=k, inference=inference, rope=rope,
                    positions=positions, attn_fn=attn_fn,
                    return_moe_aux=return_moe_aux,
                )
            # ys carry the per-layer aux scalar only when requested, so the
            # default path's scan signature (and its compiled HLO) is
            # unchanged.
            return out if return_moe_aux else (out, None)

        if config.remat:
            # A layer_scan differentiates block_fn inside loops of its own,
            # where a replay cannot be merged with the forward it replays;
            # the barrier prevent_cse puts on the inputs would stand between
            # its re-gathered weights and the matmuls that stream them.
            block_fn = jax.checkpoint(
                block_fn, policy=_remat_policy(config.remat_policy),
                prevent_cse=layer_scan is None,
            )
        if layer_scan is None:
            x, aux = jax.lax.scan(
                block_fn, x, (params.blocks, layer_keys), unroll=config.scan_unroll
            )
        else:
            x, aux = layer_scan(block_fn, x, (params.blocks, layer_keys))

        with jax.named_scope("final_norm"):
            x = rms_norm(x, eps=1e-5)  # final norm (reference model.py:133,156)
        return (x, jnp.mean(aux)) if return_moe_aux else x

    @staticmethod
    def apply(
        config: GPTConfig,
        params: GPTParams,
        tokens: Array,  # (B, T) int
        *,
        key: tp.Optional[KeyArray] = None,
        inference: bool = False,
    ) -> Array:
        """Forward pass -> logits (B, T, V) in the params' floating dtype."""
        x = GPT.hidden(config, params, tokens, key=key, inference=inference)
        return jnp.einsum("btd,vd->btv", x, params.lm_head)

    # ------------------------------------------------------------------
    # KV-cached decoding. inference-only (no dropout keys).
    # ------------------------------------------------------------------

    @staticmethod
    def prefill(
        config: GPTConfig,
        params: GPTParams,
        tokens: Array,  # (B, T) with T <= block_size
        cache: KVCache,
    ) -> tp.Tuple[Array, KVCache]:
        """Run the prompt through the model, filling cache positions [0, T).

        Returns (logits (B, T, V), cache with length=T)."""
        B, T = tokens.shape
        S, C = config.block_size, config.head_dim
        x = jnp.take(params.wte, tokens, axis=0)
        sin, cos = rope_table(C, S)
        rope = (sin[:T], cos[:T])

        def block_fn(x, block: BlockParams):
            h = rms_norm(x)
            q, k, v = GPT._project_qkv(config, block, h)  # k/v (B, T, HK, C)
            qr = apply_rope_bthc(q, rope[0], rope[1], style=config.rope_style)
            kr = apply_rope_bthc(k, rope[0], rope[1], style=config.rope_style)
            att = multihead_attention(
                qr, _repeat_kv(config, kr, 2), _repeat_kv(config, v, 2),
                impl=flash_or_blockwise(
                    config.attn_impl, T, config.attn_block_size
                ),
                inference=True,
                block_size=config.attn_block_size, layout="bthc",
                sliding_window=config.sliding_window,
                attn_sinks=config.attn_sinks,
            )
            x = GPT._attn_out_and_mlp(config, block, x, att)
            # cache stores post-norm, post-RoPE keys and raw values,
            # head-major, at the K/V head count
            return x, (kr.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

        x, (k_layers, v_layers) = jax.lax.scan(block_fn, x, params.blocks)
        pad = [(0, 0), (0, 0), (0, 0), (0, S - T), (0, 0)]
        new_cache = KVCache(
            k=jnp.pad(k_layers.astype(cache.k.dtype), pad),
            v=jnp.pad(v_layers.astype(cache.v.dtype), pad),
            length=jnp.asarray(T, jnp.int32),
        )
        x = rms_norm(x, eps=1e-5)
        logits = jnp.einsum("btd,vd->btv", x, params.lm_head)
        return logits, new_cache

    @staticmethod
    def decode_step(
        config: GPTConfig,
        params: GPTParams,
        token: Array,  # (B,) int — the newest token
        cache: KVCache,
    ) -> tp.Tuple[Array, KVCache]:
        """One incremental decode step at position cache.length.

        Precondition: cache.length < config.block_size. The cache is
        static-shape; at a full cache the dynamic_update_slice would clamp to
        the last slot and silently corrupt it, so callers (sampling engine)
        must stop or fall back to windowed forward before that.

        Returns (logits (B, V) for the next token, updated cache)."""
        B = token.shape[0]
        L, S, C = config.n_layer, config.block_size, config.head_dim
        HK = config.kv_heads
        pos = cache.length  # () int32
        x = jnp.take(params.wte, token[:, None], axis=0)  # (B, 1, D)
        sin, cos = rope_table(C, S)
        positions = pos[None]  # (1,)

        # The cache is threaded through an UNROLLED layer loop and updated
        # by a per-token COLUMN write. The r1-r4 structure (cache as scan
        # xs, new cache re-stacked from per-layer ys) forced XLA to copy
        # BOTH full (L, B, H, S, C) buffers every decode step inside the
        # chunked decode loop — measured 2.5 ms/token of pure copy at
        # 124M/B=8 on v5e, a third of the whole step (measured on an earlier
        # toolchain, not re-measured) —
        # plus per-layer stacked-slot rebuilds. A rolled scan still pays 2
        # full-cache copies/step at the inner/outer carry boundary
        # (verified on compiled HLO); the unrolled DUS chain rides the
        # decode loop's carry and aliases in place. L is static and small,
        # so the unroll is cheap to trace (decode has no remat concerns).
        def block_fn(config, carry, block_and_idx):  # `config`: the loop's (`_decode_layer_loop`)
            x, ck_all, cv_all = carry  # caches (L, B, HK, S, C)
            block, i = block_and_idx
            h = rms_norm(x)
            q, k, v = GPT._project_qkv(config, block, h)  # k/v (B, 1, HK, C)
            q = apply_rope_bthc(
                q, sin, cos, positions, style=config.rope_style
            ).transpose(0, 2, 1, 3)
            k = apply_rope_bthc(
                k, sin, cos, positions, style=config.rope_style
            ).transpose(0, 2, 1, 3)
            v = v.transpose(0, 2, 1, 3)  # (B, HK, 1, C); q (B, H, 1, C)
            ck_all = jax.lax.dynamic_update_slice(
                ck_all, k.astype(ck_all.dtype)[None], (i, 0, 0, pos, 0)
            )
            cv_all = jax.lax.dynamic_update_slice(
                cv_all, v.astype(cv_all.dtype)[None], (i, 0, 0, pos, 0)
            )
            ck = jax.lax.dynamic_slice(
                ck_all, (i, 0, 0, 0, 0), (1, B, HK, S, C)
            )[0]
            cv = jax.lax.dynamic_slice(
                cv_all, (i, 0, 0, 0, 0), (1, B, HK, S, C)
            )[0]
            # GQA: the cache holds HK heads — broadcast to the query heads
            # for the score/PV contractions (reads only, the cache itself
            # stays at K/V geometry).
            ck = _repeat_kv(config, ck, 1)
            cv = _repeat_kv(config, cv, 1)
            scores = jnp.einsum("bhqc,bhkc->bhqk", q, ck)  # (B, H, 1, S)
            col = jnp.arange(S)[None, None, None, :]
            valid = col <= pos
            if config.sliding_window:
                # Row `pos` sees count = pos + 1 keys: keep the last
                # `sliding_window` of them plus the `attn_sinks` prefix.
                keep = col > pos - config.sliding_window
                if config.attn_sinks:
                    keep |= col < config.attn_sinks
                valid &= keep
            scores = jnp.where(valid, scores, float("-inf"))
            probs = jax.nn.softmax(
                scores.astype(jnp.float32) / math.sqrt(C), axis=-1
            ).astype(q.dtype)
            att = jnp.einsum("bhqk,bhkc->bhqc", probs, cv)
            x = GPT._attn_out_and_mlp(config, block, x, att.transpose(0, 2, 1, 3))
            return (x, ck_all, cv_all), None

        carry = GPT._decode_layer_loop(config, block_fn, (x, cache.k, cache.v), params.blocks)
        x, k_new, v_new = carry
        x = rms_norm(x, eps=1e-5)
        logits = jnp.einsum("btd,vd->btv", x, params.lm_head)[:, 0]
        new_cache = KVCache(k=k_new, v=v_new, length=pos + 1)
        return logits, new_cache

    @staticmethod
    def _decode_layer_loop(config: GPTConfig, block_fn, carry, blocks, mesh=None):
        """Drive `block_fn(config, carry, (layer_params, layer_idx))` over all layers.

        Two lowerings, selected by `config.decode_layer_scan` (trade-off
        documented on the config field):

          * Python unroll (default) — the KV cache buffers thread straight
            through the unrolled DUS chain, so inside a chunked decode loop
            they alias the loop carry with ZERO full-cache copies per token
            (the r5 restructure; structural pin in tests/test_sampling.py).
            Cost: the traced decode program is O(n_layer) ops — at 12
            layers that is noise, at the 32-layer 7B shapes each chunk
            length costs noticeably more trace+compile time.
          * Rolled `lax.scan` — O(1) program size in depth (one traced
            block), at the measured cost of 2 full-cache copies per decode
            step at the inner/outer scan carry boundary (measured on an earlier
            toolchain, not re-measured:
            XLA cannot alias a while-loop carry into an enclosing loop's
            carry slot). The deep llama7b configs set this: for them,
            compile latency dominates interactive use and the copies are
            amortized by the much larger per-layer compute.

        Both run the SAME block_fn (layer index arrives as a traced scalar
        either way), so the two lowerings cannot drift beyond the rounding of
        the projection's two spellings (below) — pinned by the
        decode_layer_scan parity test in tests/test_sampling.py.

        The loop hands block_fn the `config` it projects under, because which
        spelling of the QKV projection (`_project_qkv`) reaches the weight in
        place depends on how the layer is handed over, and only this function
        knows. The unrolled loop indexes the layer out of the STACKED
        parameters: "fused"'s flat reshape of an indexed layer made the
        chip's compiler write every layer's `wqkv` out again in every decode
        step and prefill call (604 MB at the XL's widths, 1.26 ms of a 7.40 ms
        step: PERF.md section 6 PR 62; utils/hlo.py `weight_copies` counts
        them), so it always takes the per-third einsum over the (3, D, D)
        layer as it lies. The scan hands over one layer and keeps the config's
        own spelling, except under a tp > 1 serving `mesh`, where the flat
        reshape would mix the sharded feature axis into the merged one (a
        reshard a layer; the engine made this rewrite up to PR 61). No caller
        of a serving program sets `qkv_proj` for it."""
        if not config.decode_layer_scan or (mesh is not None and mesh.shape["tp"] > 1):
            config = dataclasses.replace(config, qkv_proj="split3")
        if config.decode_layer_scan:
            idx = jnp.arange(config.n_layer)
            carry, _ = jax.lax.scan(functools.partial(block_fn, config), carry, (blocks, idx))
            return carry
        for i in range(config.n_layer):
            layer = jax.tree.map(lambda a: a[i], blocks)
            carry, _ = block_fn(config, carry, (layer, jnp.asarray(i)))
        return carry

    # ------------------------------------------------------------------
    # Paged decoding (continuous-batching serving engine, sampling/serve.py)
    # ------------------------------------------------------------------

    @staticmethod
    def decode_step_paged(
        config: GPTConfig,
        params: GPTParams,
        token: Array,  # (B,) int — each slot's newest token
        cache: "PagedKVCache",
        page_table: Array,  # (B, max_pages) int32 — logical -> physical page
        lengths: Array,  # (B,) int32 — tokens already in slot b's cache
        active: Array,  # (B,) bool — False: slot is empty / mid-prefill
        attn_impl: str = "auto",
        mesh=None,  # Optional[Mesh] — tp serving mesh (parallel/serve_tp.py)
        split_k: int = 1,  # key-sequence partitions per slot (static)
    ) -> tp.Tuple[Array, "PagedKVCache"]:
        """One decode step for B independent requests at B different positions.

        Slot b writes its token's K/V at logical position lengths[b] (page
        page_table[b, lengths[b] // page_size], in-page offset lengths[b] %
        page_size) and attends to its own lengths[b] + 1 valid tokens through
        the page table — the paged counterpart of `decode_step`, with the
        SAME per-layer op order (project, per-position RoPE, column write,
        mask-then-f32-softmax attention), so the two agree token-for-token
        (parity pin in tests/test_sampling.py). Inactive slots (empty or
        mid-prefill) have their writes DROPPED (redirected out of range —
        their page rows may hold real prefilled K/V) and attend to a single
        garbage key, producing finite logits the scheduler ignores.

        The layer loop goes through `_decode_layer_loop` (decode_layer_scan
        applies). Attention dispatches per `attn_impl` — 'auto' is the
        Pallas page-table kernel on TPU, the XLA gather fallback elsewhere
        (kernels/decode_attention.py). On a tp>1 serving mesh `mesh` routes
        the kernel through its per-shard shard_map (heads split over 'tp');
        everything else in this function is spelled in plain jnp on the
        batch/feature axes, so GSPMD partitions it from the head-sharded
        pool and megatron param shardings alone — the only activation
        collectives are the two per-layer megatron all-reduces
        (_attn_out_and_mlp's wo and w_down contractions), pinned by the
        analysis/hlo_audit.py tp census.

        Returns (logits (B, V), cache with the B new K/V columns written)."""
        from midgpt_tpu.kernels.decode_attention import (
            paged_attention,
            resolve_paged_impl,
        )
        from midgpt_tpu.ops.rope import apply_rope_positions

        attn_impl = resolve_paged_impl(attn_impl)
        B = token.shape[0]
        C = config.head_dim
        ps = cache.page_size
        pos = lengths  # (B,) write positions
        active_i = active.astype(jnp.int32)
        # Valid keys per slot: the just-written token makes it lengths + 1
        # for active slots; inactive slots get 1 (the sink page's slot 0) so
        # the gather fallback's softmax never sees an all-masked row (NaN).
        attn_counts = jnp.maximum(active_i * (pos + 1), 1)
        # Inactive slots must not write at all — their page-table row is
        # real scheduler state (a mid-prefill slot's pages hold its already
        # prefilled K/V, which a sink-style write at position 0 would
        # corrupt). Redirect them past the pool so the scatter drops them.
        write_pages = jnp.where(
            active,
            jnp.take_along_axis(page_table, (pos // ps)[:, None], axis=1)[:, 0],
            cache.num_pages,
        )  # (B,)
        offs = pos % ps
        x = jnp.take(params.wte, token[:, None], axis=0)  # (B, 1, D)
        sin, cos = rope_table(C, config.block_size)
        positions = pos[:, None]  # (B, 1) — per-slot absolute positions

        def block_fn(config, carry, block_and_idx):  # `config`: the loop's (`_decode_layer_loop`)
            x, ck_all, cv_all, cks_all, cvs_all = carry  # pools (L,H,P,ps,C)
            block, i = block_and_idx
            h = rms_norm(x)
            q, k, v = GPT._project_qkv(config, block, h)  # k/v (B, 1, HK, C)
            q = apply_rope_positions(q, sin, cos, positions, style=config.rope_style)
            k = apply_rope_positions(k, sin, cos, positions, style=config.rope_style)
            # q1 (B, H, C); k1/v1 (B, HK, C) — written at K/V geometry, the
            # kernel/gather handles the query-group broadcast.
            q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]
            # One (B,)-indexed column write per pool (quantizing in int8
            # mode), in place in the decode loop carry, not a pool copy
            # (pinned) — scale side buffers included (_paged_write). The
            # attention then reads layer i of the WHOLE pool: the kernel
            # through its own page copies, the gather through a fused slice.
            ck_all, cv_all, cks_all, cvs_all = _paged_write(
                (ck_all, cv_all, cks_all, cvs_all), i, write_pages, offs,
                k1, v1, attn_impl, mesh,
            )
            att = paged_attention(
                q1, ck_all, cv_all, page_table, attn_counts, impl=attn_impl,
                k_scale=cks_all, v_scale=cvs_all, mesh=mesh, split_k=split_k,
                sliding_window=config.sliding_window,
                attn_sinks=config.attn_sinks, layer=i,
            )  # (B, H, C)
            x = GPT._attn_out_and_mlp(config, block, x, att[:, None])
            return (x, ck_all, cv_all, cks_all, cvs_all), None

        carry = GPT._decode_layer_loop(
            config,
            block_fn,
            (x, cache.k, cache.v, cache.k_scale, cache.v_scale),
            params.blocks,
            mesh,
        )
        x, k_new, v_new, ks_new, vs_new = carry
        x = rms_norm(x, eps=1e-5)
        logits = jnp.einsum("btd,vd->btv", x, params.lm_head)[:, 0]
        return logits, PagedKVCache(
            k=k_new, v=v_new, k_scale=ks_new, v_scale=vs_new
        )

    @staticmethod
    def verify_step_paged(
        config: GPTConfig,
        params: GPTParams,
        tokens: Array,  # (B, K1) int — [t_last, d_1, .., d_k] per slot
        cache: "PagedKVCache",
        page_table: Array,  # (B, max_pages) int32
        lengths: Array,  # (B,) int32 — tokens already in slot b's cache
        active: Array,  # (B,) bool
        attn_impl: str = "auto",
        mesh=None,  # Optional[Mesh] — tp serving mesh (parallel/serve_tp.py)
        split_k: int = 1,  # key-sequence partitions per slot (static)
    ) -> tp.Tuple[Array, "PagedKVCache"]:
        """Score K1 = k+1 candidate tokens per slot in ONE batched paged
        forward — the target side of speculative decoding (sampling/spec.py).

        Slot b's token t sits at absolute position lengths[b] + t: its K/V
        is written there (same advanced-index scatter as decode_step_paged)
        and its query attends to lengths[b] + t + 1 keys through the page
        table — all K1 rows are written before the gather, so the per-row
        count IS the causal mask (kernels/decode_attention.py
        paged_verify_attention). Row t's logits score the token at position
        lengths[b] + t + 1, i.e. row 0 judges d_1 and row K1-1 supplies the
        bonus distribution.

        Positions past the accepted prefix hold REJECTED speculative K/V
        after the caller's rollback — that is deliberate: rollback is
        host-side only (length counters reset, tail pages freed), the pool
        is never rewritten, and the stale columns are masked by every later
        read until the slot grows back over them (write-before-read, the
        page-aligned rollback invariant, docs/SERVING.md). Inactive slots
        write nothing (out-of-range redirect) and attend to the single sink
        key. Same per-layer op order as decode_step_paged, so greedy
        speculative serving stays token-identical to plain paged decode
        (pinned by tests/test_spec.py).

        Precondition (scheduler-enforced): lengths[b] + K1 <= block_size and
        the page table covers position lengths[b] + K1 - 1 for active slots.

        Returns (logits (B, K1, V), cache with the B*K1 columns written)."""
        from midgpt_tpu.kernels.decode_attention import (
            paged_verify_attention,
            resolve_paged_impl,
        )
        from midgpt_tpu.ops.rope import apply_rope_positions

        attn_impl = resolve_paged_impl(attn_impl)
        B, K1 = tokens.shape
        C = config.head_dim
        ps = cache.page_size
        t_idx = jnp.arange(K1, dtype=jnp.int32)
        positions = lengths[:, None] + t_idx[None, :]  # (B, K1)
        active_i = active.astype(jnp.int32)
        attn_counts = jnp.maximum(active_i[:, None] * (positions + 1), 1)
        write_pages = jnp.where(
            active[:, None],
            jnp.take_along_axis(page_table, positions // ps, axis=1),
            cache.num_pages,
        )  # (B, K1); inactive writes dropped via XLA oob-scatter semantics
        offs = positions % ps
        x = jnp.take(params.wte, tokens, axis=0)  # (B, K1, D)
        sin, cos = rope_table(C, config.block_size)

        def block_fn(config, carry, block_and_idx):  # `config`: the loop's (`_decode_layer_loop`)
            x, ck_all, cv_all, cks_all, cvs_all = carry  # pools (L,H,P,ps,C)
            block, i = block_and_idx
            h = rms_norm(x)
            q, k, v = GPT._project_qkv(config, block, h)  # k/v (B, K1, HK, C)
            q = apply_rope_positions(q, sin, cos, positions, style=config.rope_style)
            k = apply_rope_positions(k, sin, cos, positions, style=config.rope_style)
            # (B, K1)-indexed column write — the same in-place write as the
            # decode/prefill ones (quantizing in int8 mode, scale buffers
            # riding along); a slot's K1 rows are consecutive positions.
            ck_all, cv_all, cks_all, cvs_all = _paged_write(
                (ck_all, cv_all, cks_all, cvs_all), i, write_pages, offs,
                k, v, attn_impl, mesh,
            )
            att = paged_verify_attention(
                q, ck_all, cv_all, page_table, attn_counts, impl=attn_impl,
                k_scale=cks_all, v_scale=cvs_all, mesh=mesh, split_k=split_k,
                sliding_window=config.sliding_window,
                attn_sinks=config.attn_sinks, layer=i,
            )  # (B, K1, H, C)
            x = GPT._attn_out_and_mlp(config, block, x, att.astype(x.dtype))
            return (x, ck_all, cv_all, cks_all, cvs_all), None

        carry = GPT._decode_layer_loop(
            config,
            block_fn,
            (x, cache.k, cache.v, cache.k_scale, cache.v_scale),
            params.blocks,
            mesh,
        )
        x, k_new, v_new, ks_new, vs_new = carry
        x = rms_norm(x, eps=1e-5)
        logits = jnp.einsum("btd,vd->btv", x, params.lm_head)
        return logits, PagedKVCache(
            k=k_new, v=v_new, k_scale=ks_new, v_scale=vs_new
        )

    @staticmethod
    def prefill_paged_chunk(
        config: GPTConfig,
        params: GPTParams,
        tokens: Array,  # (B, T_c) int — one prompt chunk a row, padded
        start: Array,  # (B,) int32 — absolute position of tokens[b, 0]; or ()
        n_valid: Array,  # (B,) int32 — real tokens in row b (rest is pad); or ()
        cache: "PagedKVCache",
        page_table: Array,  # (B, max_pages) int32
        attn_impl: str = "auto",
        mesh=None,  # Optional[Mesh] — tp serving mesh (parallel/serve_tp.py)
    ) -> tp.Tuple[Array, "PagedKVCache"]:
        """Prefill the prompt chunks of B requests, row b's being
        [start[b], start[b] + n_valid[b]), into their pages; each row
        attends causally to its own chunk plus everything its slot already
        holds ([0, start[b]) — earlier chunks, or a prefix the cache handed
        it). A row with n_valid == 0 is an empty place: it writes nothing
        and reads one masked-in key of whatever page its table row names
        (the engine: the sink page), and its logits are garbage.

        The rows of a round ride ONE program so that the round reads the
        weights once (sampling/serve.py `PREFILL_ROWS`), and the program
        hands back the ONE logits row a slot that the engine samples from,
        (B, V): the row at each slot's last valid position. Called with a
        SCALAR `start` and `n_valid` (one row, B = 1) it hands back every
        row's logits, (1, T_c, V), for a caller that compares them all.

        Chunking is what lets the scheduler interleave long-prompt admission
        with running decodes: each serve round spends at most T_c prompt
        tokens a slot before the batch decodes again (docs/SERVING.md).
        T_c is static — the engine pads the tail chunk and passes n_valid;
        pad positions are redirected to an out-of-range page index so the
        scatter DROPS them (XLA oob-scatter semantics) instead of clobbering
        allocated pages, and pad logits are garbage the caller ignores.

        Attention is ONE call of the multi-row paged attention a layer
        (kernels/decode_attention.py `paged_verify_attention`, the pattern
        of `verify_step_paged`: all T_c rows written, then row t reads
        start + t + 1 keys through the page table). `attn_impl` chooses
        both lowerings, as it does for decode and verify: 'kernel' (what
        'auto' resolves to on a TPU) stores the chunks' K/V with the
        in-place Pallas write and reads them through the paged-attention
        template at n_rows = T_c, each row sweeping its own pages, so that
        this program holds no scatter and no gather on the pool and keeps
        it in the layout the kernels read; 'gather' is the XLA scatter and
        gather of the same arithmetic. `mesh` routes write and read per tp
        shard.

        Returns (logits (B, V) — or (1, T_c, V) for a scalar `start` —,
        updated cache)."""
        from midgpt_tpu.kernels.decode_attention import paged_verify_attention, resolve_paged_impl
        from midgpt_tpu.ops.rope import apply_rope_positions

        attn_impl = resolve_paged_impl(attn_impl)
        every_row = jnp.ndim(start) == 0  # the one-row call: all T_c logits
        start, n_valid = jnp.reshape(start, (-1,)), jnp.reshape(n_valid, (-1,))
        _, T_c = tokens.shape
        C = config.head_dim
        ps = cache.page_size
        t_idx = jnp.arange(T_c, dtype=jnp.int32)
        positions = start[:, None] + t_idx  # (B, T_c)
        valid = t_idx < n_valid[:, None]
        # Pad writes go out of range -> dropped by the scatter.
        write_pages = jnp.where(
            valid,
            jnp.take_along_axis(page_table, positions // ps, axis=1),
            cache.num_pages,
        )
        offs = positions % ps
        x = jnp.take(params.wte, tokens, axis=0)  # (B, T_c, D)
        sin, cos = rope_table(C, config.block_size)
        # Row t of a chunk attends to start + t + 1 keys; pad rows clamp to
        # the last valid count (their output is discarded), an empty row's
        # to one key.
        attn_counts = jnp.maximum(
            jnp.minimum(positions, (start + n_valid)[:, None] - 1) + 1, 1
        )  # (B, T_c)

        def block_fn(config, carry, block_and_idx):  # `config`: the loop's (`_decode_layer_loop`)
            x, ck_all, cv_all, cks_all, cvs_all = carry
            block, i = block_and_idx
            h = rms_norm(x)
            q, k, v = GPT._project_qkv(config, block, h)  # k/v (B, T_c, HK, C)
            qr = apply_rope_positions(q, sin, cos, positions, style=config.rope_style)
            kr = apply_rope_positions(k, sin, cos, positions, style=config.rope_style)
            # one (B, T_c)-indexed column write (quantized with per-vector
            # scales in int8 mode).
            ck_all, cv_all, cks_all, cvs_all = _paged_write(
                (ck_all, cv_all, cks_all, cvs_all), i, write_pages, offs,
                kr, v, attn_impl, mesh,
            )
            # Every chunk row reads its slot's pages of layer i of the WHOLE
            # pool under its own count. The innermost scope names the custom
            # call in the device trace: the benchmark reads `closed_call.<n>`
            # as the DECODE kernel (PERF.md §7).
            with jax.named_scope("prefill_attn"):
                att = paged_verify_attention(
                    qr, ck_all, cv_all, page_table, attn_counts,
                    k_scale=cks_all, v_scale=cvs_all, impl=attn_impl, mesh=mesh,
                    sliding_window=config.sliding_window,
                    attn_sinks=config.attn_sinks, layer=i,
                )  # (B, T_c, H, C)
            x = GPT._attn_out_and_mlp(config, block, x, att.astype(x.dtype))
            return (x, ck_all, cv_all, cks_all, cvs_all), None

        carry = GPT._decode_layer_loop(
            config,
            block_fn,
            (x, cache.k, cache.v, cache.k_scale, cache.v_scale),
            params.blocks,
            mesh,
        )
        x, k_new, v_new, ks_new, vs_new = carry
        if not every_row:
            # the last valid row of each slot, before the head: the lm_head
            # runs over B rows, not B x T_c
            last = jnp.maximum(n_valid - 1, 0)[:, None, None]
            x = jnp.take_along_axis(x, last, axis=1)  # (B, 1, D)
        x = rms_norm(x, eps=1e-5)
        logits = jnp.einsum("btd,vd->btv", x, params.lm_head)
        return logits if every_row else logits[:, 0], PagedKVCache(
            k=k_new, v=v_new, k_scale=ks_new, v_scale=vs_new
        )

    # -- the serving members of the family contract (models/__init__.py) --
    serve_counters = None  # no counters of its own beside the engine's
    prefill_batched = True  # prefill_paged_chunk takes B rows, hands back (B, V)

    @staticmethod
    def prefill_rows(config: GPTConfig, dense_rows: int) -> int:
        """Token rows a prefill call should carry: every weight sees every row,
        so the rows a dense weight wants (sampling/serve.py `PREFILL_ROWS`)."""
        return dense_rows

    @staticmethod
    def cache_kinds(config: GPTConfig) -> tp.Tuple[CacheKind, ...]:
        """One kind: every layer keeps K/V alike (windowed, if the model is)."""
        return (CacheKind("kv", config.sliding_window, config.attn_sinks),)

    @staticmethod
    def init_cache(config: GPTConfig, num_pages: tp.Sequence[int], page_size: int = 8,
                   dtype=jnp.bfloat16, kernel_layout: bool = False) -> PagedKVCache:
        return PagedKVCache.init(config, num_pages[0], page_size, dtype, kernel_layout)

    kernel_sweep_whole = True  # every layer's decode attention is this one kernel call

    @staticmethod
    def kernel_sweep(config: GPTConfig, cache: PagedKVCache):
        """(pool shape, q rows a pool head, window, sinks) of the decode
        kernel's sweep, for the engine's block counters."""
        return cache.k.shape, config.kv_groups, config.sliding_window, config.attn_sinks

    @staticmethod
    def count_params(params: GPTParams) -> int:
        """Parameter count excluding the duplicated tied embedding
        (reference model.py:161-164)."""
        total = sum(x.size for x in jax.tree.leaves(params))
        return total - params.lm_head.size

    @staticmethod
    def cast_params(params: GPTParams, dtype) -> GPTParams:
        """The compute copy: every floating leaf in `dtype`."""
        return jax.tree.map(
            lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p,
            params,
        )

    # Decay applies to ALL params, including norm scales and embeddings, as in
    # the reference (training/optim.py).
    weight_decay_mask = None
    # No counters of its own for the train loop's logged steps.
    route_stats = None

    @staticmethod
    def param_specs(config, tree, mesh):
        """Placement rule: GPipe layer-axis sharding when the mesh has a real
        'pp' axis (parallel/pipeline.py), else Megatron tp x fsdp
        (parallel/tp.py), which with mesh tp=1 reduces to the plain FSDP rule
        exactly (pinned by test_tp.py). `config`: the ExperimentConfig."""
        if mesh.shape["pp"] > 1:
            # layer axis over 'pp', large leaves additionally over 'fsdp'
            from midgpt_tpu.parallel.pipeline import pipeline_param_specs

            return pipeline_param_specs(tree, mesh, config.shard_model, config.fsdp_min_size)
        from midgpt_tpu.parallel.tp import tp_param_specs

        return tp_param_specs(
            tree, mesh, config.shard_model, config.fsdp_min_size, vocab_parallel=config.tp_vocab
        )

    @staticmethod
    def flops_per_token(cfg: GPTConfig, seq_len: tp.Optional[int] = None, stats=None) -> float:
        """Training FLOPs/token: 6N for the matmuls (fwd 2N + bwd 4N) plus the
        12*L*D*T attention-scores term (PaLM appendix B accounting)."""
        del stats
        T = seq_len or cfg.block_size
        D, L, V = cfg.n_embd, cfg.n_layer, cfg.vocab_size
        if cfg.n_experts > 0:
            # ACTIVE-expert accounting (the MoE convention): top_k expert MLPs
            # + the router per token. The masked-dense lowering EXECUTES all E
            # experts, so reported MFU under-counts by E/top_k there — honest
            # for the useful-FLOPs metric.
            mlp = min(cfg.moe_top_k, cfg.n_experts) * 8 * D * D + cfg.n_experts * D
        else:
            mlp = 8 * D * D
        n_params = V * D + L * (4 * D * D + mlp + 2 * cfg.head_dim) + V * D
        # Count the tied embedding once, like reference count_params (model.py:161).
        n_params -= V * D
        return 6.0 * n_params + 12.0 * L * D * T
