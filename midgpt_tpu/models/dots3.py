"""dots3-note (`model_type: dots3_note`): latent attention (MLA) of TWO
geometries in one stack, the full-attention layers with LEARNED SPARSE attention
(an indexer scores every cached token for every query, the exact top
`index_topk` are attended and nothing else), the sliding layers a window of 513
over a wider latent of their own; a headwise output gate on both; pre-norm; a
sigmoid-routed mixture of 256 experts beside a shared one. SERVED (sample.py,
ServeEngine); training is refused by name (`check_training`).

Source: https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json
(288B-A17B: 46 layers, hidden 5,120; 13 `full_attention` layers of 128 heads,
q_lora 1,024, kv_lora 512, qk 128 + 64, v 128, rotary base 8e7, indexer of 64
heads of 128, top 2,048; 33 `sliding_attention` layers of 64 heads, q_lora
1,024, kv_lora 1,024, qk 192 + 64, v 128, base 5e4, window 513; one leading
dense SwiGLU of 13,824, then 256 experts of 1,536, top-8, one shared expert;
vocabulary 152,064, untied head). Only the language model: the vision and audio
towers and the MTP module are left unloaded. The layers differ by KIND, so the
parameters are a tuple of per-layer pytrees and every forward is a Python loop.

RMSNorm with a gain, eps 1e-5; no bias on any projection. A layer (pre-norm):

    h = x + Attn(n_1(x));   x' = h + FFN(n_2(h))

Attention of either kind, u = n_1(x), with that kind's OWN ranks, heads, widths
and rotary base (`Dots3Config.geom`):
    c_q = r_q n_q(W_qa u);  q = W_qb c_q as heads of [q_n; q_r];
    [c_kv; k_r] = W_kva u;  c = r_kv n_kv(c_kv);  rotate-half rotary on q_r and
    on the ONE k_r;  [k_n; v] = W_kvb c a head;  scores (q_n.k_n + q_r.k_r) /
    sqrt(nope + rope);  g = sigmoid(W_g u), ONE scalar a head;  out = W_o [g_h o_h].
    r_q = sqrt(n_embd / q_lora_rank), r_kv = sqrt(n_embd / kv_lora_rank)
    (`apply_mla_qkv_lora_rescale`), constants after the latent norms.
  full (`full_attention`): the INDEXER q^I = W_iq c_q as (64, 128), k^I =
    LayerNorm(W_ik u) (one key a token), rotary (the layer's base) on the
    leading `qk_rope_head_dim` channels of both, w = W_iw u / sqrt(64 x 128);
    I[t, s] = sum_h w[t, h] relu(q^I[t, h] . k^I[s]) in float32 for s <= t; S_t
    = the min(t + 1, index_topk) positions with the largest I[t, .], ties to the
    LOWER position, EXACT; the softmax runs over S_t only, every head alike.
  window (`sliding_attention`): no indexer; key s visible to query t iff
    t - sliding_window < s <= t.
FFN: layers < `n_dense_layers` a SwiGLU of `dense_width`; the others `ops/moe.py`
(`route`: sigmoid in float32, the top 8 of s + bias, weights the selected s
renormalised, times `routed_scaling_factor` 1) over the experts HELD here
(`[expert_offset, expert_offset + n_experts_held)`), plus the shared expert.

WHAT IS CACHED (models/gpt.py `ServeCache`: `pools` = ((rows, index keys),
(window rows,)), a kind's arrays under one page table). Kind `latent` (window 0; the full layers): a token's row [c; k_r
rotated] (576 values, 640 lanes on the kernel path) AND, beside it, its rotated
index key (128 values): two arrays that live and die with the same pages. Kind
`window_latent` (window 513; the sliding layers): ONE array of rows of 1,088
values (1,152 lanes); the engine frees a page once every future query's window
has passed it.

Attention on the paged path:
  full, decode     `dsa_index`: the slot's cached index keys swept in blocks
                   through the page table (XLA), a score row a slot;
                   `dsa_topk`: `jax.lax.top_k` (exact, ties to the lower
                   position); `attn_select`: ONE gather of the selected rows
                   (positions -> page and offset; `index_topk` x 1,280 B a slot
                   a layer whatever the context) and the ABSORBED arithmetic of
                   models/pangu_ultra.py on them (W_kvb's key half folded into
                   the query, its value half into the output).
  full, prefill    the chunk's rows and index keys are written first; I for the
                   chunk's rows against the whole context in key blocks; each
                   row's k-th largest score by 32 counting passes over the
                   scores' bits (`kth_largest`: exact, no sort); then the
                   slot's cached latents swept in blocks of 1,024, each block
                   EXPANDED to K and V, the selection as a MASK (dense FLOPs,
                   no gather of selected rows). On a TPU that sweep is ONE
                   Mosaic call a layer (kernels/latent_prefill.py, PR 60): grid
                   (64 groups of 2 heads, key blocks innermost); a step holds
                   in VMEM one block of the slot's rows (from one contiguous
                   copy gathered through the page table before the call; the
                   pool is not copied), the group's slices of W_kvb and of the
                   queries, a head's expanded (1,024, 256) K_n and V, its (512,
                   1,024) float32 score tile and probabilities, and the
                   group's running (m, l, acc) across its sweep: no score,
                   probability or expanded tile reaches HBM (as an XLA loop
                   they were ~0.9 GB a block and the sweep was HBM-bound). The
                   selection AND each row's visibility arrive as ONE int8
                   operand `keep` (T, S) built from `kth_largest`'s (thr, need)
                   by `selection_mask` (ties to the lower position), not as
                   per-row scalars: 512 stacked scalar counts are refused by
                   Mosaic; only the live block count rides scalar prefetch.
                   Not a spec of kernels/attention_template.py, whose body
                   scores pool rows as stored and copies pages by hand: a
                   sibling file leaves the decode kernel's text as it is.
                   Off the TPU: `_prefill_sparse_sweep`, the same arithmetic
                   as an XLA loop (the tests' oracle).
  window, decode   kernels/attention_template.py with `v_lanes` AND
                   `sliding_window` over the window kind's own logical table
                   (TPU; absorbed: 64 query rows against one pool head of 1,152
                   lanes); off the TPU the XLA gather of the window's pages.
  window, prefill  XLA: the pages that [start - window, start + chunk) touches,
                   latents expanded.

Departures from the published description (the configuration file lists them
under `assumed`): the indexer's Hadamard rotation of q^I and k^I is left out
(orthogonal: q.k unchanged), and its FP8 storage (bf16 in the pool).
"""

from __future__ import annotations

import dataclasses
import math
import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.models.gpt import CacheKind, ServeCache, _paged_write
from midgpt_tpu.ops.attention import visible_mask
from midgpt_tpu.ops.moe import (
    moe_count_decode, moe_count_dropped, moe_counters_init, moe_serve_counters, moe_serving, swiglu,
)
from midgpt_tpu.ops.norms import rms_norm
from midgpt_tpu.ops.online_softmax import M_INIT, MASK, finalize, online_block
from midgpt_tpu.ops.rope import apply_rope_leading, rope_table
from midgpt_tpu.utils.pytree import pytree_dataclass

Array = jax.Array
KeyArray = jax.Array

FAMILY = "dots3_note"
LATENT, WINDOW_LATENT = "latent", "window_latent"  # the two kinds of paged cache
SLIDING, FULL = "sliding_attention", "full_attention"  # the published `layer_types`
SCOPE = {LATENT: "attn_sparse", WINDOW_LATENT: "attn_window"}
KEY_BLOCK = 1024  # cached rows a step of a sweep (index keys, prefill latents) reads at once
LAYER_NORM_EPS = 1e-6  # the index key's LayerNorm (weight and bias over its 128 channels)
# What `init` seeds beside the usual (truncated normal / sqrt(fan_in) matrices, every other norm gain 1), and the
# readings on the chip that forced each (PERF.md section 6 PR 51; logits error in units of the reference logits'
# standard deviation, 32 compared rows, bf16 program against the float32 reference):
# Q_NORM_INIT, the QUERY latent norm's gain, both kinds. With random projections and the rescale constants (r_q r_kv =
#   sqrt(50) on a full layer's q_n . k_n, 5 on a sliding layer's) the scores' standard deviation is ~4.5 / ~3.4: a
#   softmax that sharp is close to an argmax, and bf16 rounding of a score moves whole rows (program RMS 0.32, the
#   reference with 8-bit matrices 0.96: no limit lies between). A trained model's projections absorb the constants. At
#   0.35 the scores' deviation is ~1.6 / ~1.2 (program 0.077, 8-bit 0.44, dense-attention control 0.83); the selection
#   is unchanged (q^I scales with c_q, and a top-k does not see a positive factor).
# WTE_INIT_STD, the embedding's scale. At 1 / sqrt(D) a token's own row (RMS 0.014) is a seventh of its first
#   attention output, so after layer 0 every stream is mostly an average of OTHER tokens' values, nearly the same
#   vector for all: the seeded router then sends a step's pairs to few experts (`moe.load_max_over_mean` 2.55 at
#   Q_NORM_INIT 0.35; 1.20 at gain 1, where the attention is an argmax and differs by token) and the boundary trades of
#   a selection made from bf16 inputs move the rows past index_topk most (0.107 against 0.03). At unit scale (what a
#   trained model's first norm sees: a token's own row) the program reads RMS 1.06e-2, both controls 1.06e-1 to
#   1.09e-1, and the router 1.17.
Q_NORM_INIT = 0.35
WTE_INIT_STD = 1.0


class Geom(tp.NamedTuple):
    """One attention kind's own sizes."""

    kind: str
    n_head: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    window: int  # keys a query sees at most; 0: every earlier key (of which the indexer selects)

    @property
    def qk(self) -> int:
        return self.nope + self.rope

    @property
    def latent_dim(self) -> int:
        """What a token keeps in a layer's cache: the normed, rescaled latent and the rotated shared key."""
        return self.kv_rank + self.rope


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    """Model shape, by the published keys' meaning. The first five fields are
    named as `GPTConfig` names them (models/__init__.py)."""

    block_size: int  # serving cap on prompt + output (the source declares 524,288 positions)
    vocab_size: int  # rows of wte / lm_head held here
    n_layer: int  # num_hidden_layers
    n_head: int  # num_attention_heads (the full layers')
    n_embd: int  # hidden_size
    layer_types: tp.Tuple[str, ...] = ()  # one a layer, as run (a depth cut names the layers it keeps)
    n_dense_layers: int = 1  # first_k_dense_replace: the leading layers whose FFN is a dense SwiGLU
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    swa_n_head: int = 64  # swa_num_attention_heads
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window: int = 513  # sliding_window_size: a query sees itself and 512 before
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    mla_rescale: bool = True  # apply_mla_qkv_lora_rescale
    headwise_gate: bool = True  # attention_gate_type / swa_attention_gate_type "headwise"
    dense_width: int = 13824  # intermediate_size
    n_experts: int = 256  # n_routed_experts: the router's width
    n_experts_held: int = 256  # experts whose weights live here
    expert_offset: int = 0
    moe_top_k: int = 8  # num_experts_per_tok
    expert_width: int = 1536  # moe_intermediate_size (routed and shared)
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    moe_renormalize: bool = True  # norm_topk_prob
    rms_norm_eps: float = 1e-5
    family: str = FAMILY  # discriminates model_config in config.json

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(str(t) for t in self.layer_types))
        if self.family != FAMILY:
            raise ValueError(f"family={self.family!r} is not {FAMILY!r}")
        if len(self.layer_types) != self.n_layer or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types must name {SLIDING!r} or {FULL!r} for each of n_layer={self.n_layer} layers")
        if self.qk_rope_head_dim % 2 or self.swa_qk_rope_head_dim % 2 or self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("rotate-half needs even rotary widths, and the indexer rotates its leading qk_rope_head_dim channels")
        if not (0 <= self.expert_offset and self.expert_offset + self.n_experts_held <= self.n_experts):
            raise ValueError(
                f"experts held [{self.expert_offset}, {self.expert_offset + self.n_experts_held}) "
                f"lie outside the router's {self.n_experts}"
            )
        if not 1 <= self.moe_top_k <= self.n_experts:
            raise ValueError(f"moe_top_k={self.moe_top_k} must be in [1, n_experts={self.n_experts}]")

    # -- what the runtime reads of any model config (models/__init__.py) --
    def model(self):
        return Dots3

    def check_experiment(self, config) -> None:
        m = config.mesh
        over = {a: getattr(m, a) for a in ("fsdp", "sp", "tp", "pp", "ep") if getattr(m, a) not in (1, -1)}
        if over or config.shard_model:
            raise ValueError(
                f"{FAMILY}: no mesh axis is wired (got {over or 'shard_model=True'}): no sharding rule "
                "for the per-layer parameter tuple, no exchange of routed tokens over 'ep'"
            )
        if config.spec_layers:
            raise ValueError(f"{FAMILY}: spec_layers needs a verify step over the two-kind latent cache, which is not wired")

    def check_training(self, who: str) -> None:
        raise NotImplementedError(
            f"{who} cannot train a {FAMILY} model: no backward is wired through the indexer's top-k selection "
            "(the published recipe trains the indexer by a separate loss against the dense attention), "
            "ops/moe.py's serving path is forward only, and at 16 B a parameter no cut inside the floors fits a "
            "chip. Serve it: sample.py --engine=continuous, ServeEngine."
        )

    def check_serving(self, who: str) -> None:
        """sample.py's continuous engine and ServeEngine serve this family."""

    # -- the two attention kinds, the two FFN kinds --
    def attn_kind(self, i: int) -> str:
        return WINDOW_LATENT if self.layer_types[i] == SLIDING else LATENT

    def geom(self, kind: str) -> Geom:
        if kind == LATENT:
            return Geom(kind, self.n_head, self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
                        self.qk_rope_head_dim, self.v_head_dim, self.rope_theta, 0)
        return Geom(kind, self.swa_n_head, self.swa_q_lora_rank, self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                    self.swa_qk_rope_head_dim, self.swa_v_head_dim, self.swa_rope_theta, self.sliding_window)

    def rescale(self, rank: int) -> float:
        """The constant a latent of `rank` is multiplied by after its norm."""
        return math.sqrt(self.n_embd / rank) if self.mla_rescale else 1.0

    def mlp_kind(self, i: int) -> str:
        return "dense" if i < self.n_dense_layers else "moe"

    def layers_of(self, kind: str) -> tp.Tuple[int, ...]:
        return tuple(i for i in range(self.n_layer) if self.attn_kind(i) == kind)

    @property
    def pool_layers(self) -> tp.Tuple[tp.Tuple[str, int], ...]:
        """(kind, index within that kind's pool) of every layer."""
        seen = {LATENT: 0, WINDOW_LATENT: 0}
        out = []
        for i in range(self.n_layer):
            kind = self.attn_kind(i)
            out.append((kind, seen[kind]))
            seen[kind] += 1
        return tuple(out)

    @property
    def moe_layers(self) -> tp.Tuple[int, ...]:
        return tuple(i for i in range(self.n_layer) if self.mlp_kind(i) == "moe")


@pytree_dataclass
class IndexParams:
    w_q: Array  # (index_n_heads * index_head_dim, q_lora_rank): from the query latent c_q
    w_k: Array  # (index_head_dim, D): ONE key a token
    k_norm_w: Array  # (index_head_dim,) the key's LayerNorm
    k_norm_b: Array  # (index_head_dim,)
    w_w: Array  # (index_n_heads, D): the heads' weights


@pytree_dataclass
class MLAParams:
    w_qa: Array  # (q_rank, D)
    q_norm: Array  # (q_rank,)
    w_qb: Array  # (H * (nope + rope), q_rank)
    w_kva: Array  # (kv_rank + rope, D)
    kv_norm: Array  # (kv_rank,)
    w_kvb: Array  # (H * (nope + v), kv_rank): a head's rows are [k_n (nope); v]
    w_g: tp.Optional[Array]  # (H, D) the headwise gate; None without
    wo: Array  # (D, H * v)
    index: tp.Optional[IndexParams]  # a full layer's indexer; None in a sliding layer


@pytree_dataclass
class SwiGLUParams:
    w_gate: Array  # (F, D)
    w_up: Array  # (F, D)
    w_down: Array  # (D, F)


@pytree_dataclass
class MoEParams:
    router: Array  # (n_experts, D)
    router_bias: Array  # (n_experts,) `noaux_tc`'s correction bias (a buffer a balancing rule moves; seeded 0)
    w_gate: Array  # (n_experts_held, F, D)
    w_up: Array  # (n_experts_held, F, D)
    w_down: Array  # (n_experts_held, D, F)
    shared: SwiGLUParams  # width n_shared_experts * F, whole on every chip


@pytree_dataclass
class LayerParams:
    norm1: Array  # (D,) before attention
    attn: MLAParams
    norm2: Array  # (D,) before the FFN
    mlp: tp.Union[SwiGLUParams, MoEParams]


@pytree_dataclass
class Dots3Params:
    wte: Array  # (V, D)
    layers: tp.Tuple[LayerParams, ...]
    final_norm: Array  # (D,)
    lm_head: Array  # (V, D), untied


_F32_LEAVES = ("norm1", "norm2", "final_norm", "q_norm", "kv_norm", "k_norm_w", "k_norm_b", "router", "router_bias")


def _linear(key: KeyArray, out_features: int, in_features: int) -> Array:
    return jax.random.truncated_normal(key, -2.0, 2.0, (out_features, in_features)) / math.sqrt(in_features)


def _norm(c: Dots3Config, x: Array, w: Array, gain: float = 1.0) -> Array:
    """Weighted RMSNorm over the trailing axis in float32, times the constant `gain`."""
    return (rms_norm(x.astype(jnp.float32), w.astype(jnp.float32), c.rms_norm_eps) * gain).astype(x.dtype)


def _layer_norm(x: Array, w: Array, b: Array) -> Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + LAYER_NORM_EPS) * w + b).astype(x.dtype)


def _write(pool: Array, li: int, pages: Array, offs: Array, rows: Array, impl: str) -> Array:
    """`rows` (N, width) into layer `li` of a ONE-array pool at (pages, offs): `_paged_write`'s one-tensor form."""
    return _paged_write((pool, None, None, None), jnp.asarray(li), pages, offs, rows[:, None, :], None, impl, None)[0]


def _add64(acc: Array, x: Array) -> Array:
    """acc (2,) uint32 (low, high) + x (a non-negative int32 scalar), with the carry."""
    lo = acc[0] + x.astype(jnp.uint32)
    return jnp.stack([lo, acc[1] + (lo < acc[0]).astype(jnp.uint32)])


def sortable_bits(x: Array) -> Array:
    """float32 -> uint32 whose unsigned order is the floats' (-inf lowest; -0.0
    is made +0.0 first, so that equal floats have equal bits)."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32) + 0.0, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def kth_largest(key: Array, k: int) -> tp.Tuple[Array, Array]:
    """key (R, S) uint32 -> (thr (R,): each row's k-th largest value (k <= S),
    need (R,): how many of the entries EQUAL to it belong to the top k). Exact,
    by 32 counting passes (one a bit, from the top), no sort."""
    def body(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[:, None], axis=1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, body, jnp.zeros(key.shape[:1], jnp.uint32))
    return thr, k - jnp.sum(key > thr[:, None], axis=1, dtype=jnp.int32)


def selected(key: Array, thr: Array, need: Array, eq_before: Array) -> tp.Tuple[Array, Array]:
    """Which columns of the block `key` (R, S) lie in their row's top k: above
    `thr`, or equal to it among the first `need` such columns of the row
    (`eq_before`: equal columns in the blocks before this one). -> (mask, eq_before after)."""
    eq = key == thr[:, None]
    rank = eq_before[:, None] + jnp.cumsum(eq, axis=1, dtype=jnp.int32)
    return (key > thr[:, None]) | (eq & (rank <= need[:, None])), rank[:, -1]


def selection_mask(scores: Array, thr: Array, need: Array, block: int = KEY_BLOCK) -> Array:
    """`selected` over WHOLE rows at once, from the index scores (R, S) whose
    `sortable_bits` gave `thr` and `need` -> (R, S) bool, with no (R, S) count:
    among a row's columns EQUAL to `thr` the first `need` are those up to the
    column of the need-th one, found in two steps (the equal columns counted a
    block of `block`, then a cumulative count inside the one block where the
    need-th lies). The bits are formed again wherever they are compared, and
    the one block a row is GATHERED from the scores: a gather of the bits would
    keep a second (R, S) copy of them alive beside `kth_largest`'s."""
    R, S = scores.shape
    block = min(block, S)
    blocks = jnp.pad(scores, ((0, 0), (0, -S % block)), constant_values=-jnp.inf).reshape(R, -1, block)  # padding lies past every real column
    tot = jnp.sum(sortable_bits(blocks) == thr[:, None, None], axis=2, dtype=jnp.int32)  # (R, blocks)
    upto = jnp.cumsum(tot, axis=1)
    b = jnp.argmax(upto >= need[:, None], axis=1)  # the first block that reaches `need`
    left = need - jnp.take_along_axis(upto - tot, b[:, None], axis=1)[:, 0]  # equal columns still to take inside it
    inner = jnp.cumsum(sortable_bits(jnp.take_along_axis(blocks, b[:, None, None], axis=1)[:, 0]) == thr[:, None],
                       axis=1, dtype=jnp.int32)
    cut = b * block + jnp.argmax(inner >= left[:, None], axis=1)  # the column of the need-th equal entry
    key, col = sortable_bits(scores), jnp.arange(S, dtype=jnp.int32)
    return (key > thr[:, None]) | ((key == thr[:, None]) & (col[None, :] <= cut[:, None]))


class Dots3:
    """Namespace of pure functions over (Dots3Config, Dots3Params)."""

    weight_decay_mask = None
    route_stats = None
    verify_step_paged = None  # no speculative verify over the two-kind cache (the engine refuses a draft)
    prefill_batched = False  # one row a call: two page tables a slot, a sweep bounded by ONE slot's length

    @staticmethod
    def init(config: Dots3Config, key: KeyArray) -> Dots3Params:
        c = config
        D = c.n_embd

        def init_mla(k: KeyArray, g: Geom) -> MLAParams:
            ks = jax.random.split(k, 9)
            index = None
            if g.kind == LATENT:
                index = IndexParams(
                    w_q=_linear(ks[6], c.index_n_heads * c.index_head_dim, g.q_rank), w_k=_linear(ks[7], c.index_head_dim, D),
                    k_norm_w=jnp.ones((c.index_head_dim,)), k_norm_b=jnp.zeros((c.index_head_dim,)),
                    w_w=_linear(ks[8], c.index_n_heads, D),
                )
            return MLAParams(
                w_qa=_linear(ks[0], g.q_rank, D), q_norm=jnp.full((g.q_rank,), Q_NORM_INIT),
                w_qb=_linear(ks[1], g.n_head * g.qk, g.q_rank),
                w_kva=_linear(ks[2], g.latent_dim, D), kv_norm=jnp.ones((g.kv_rank,)),
                w_kvb=_linear(ks[3], g.n_head * (g.nope + g.v), g.kv_rank),
                w_g=_linear(ks[4], g.n_head, D) if c.headwise_gate else None,
                wo=_linear(ks[5], D, g.n_head * g.v), index=index,
            )

        def init_swiglu(k: KeyArray, F: int) -> SwiGLUParams:
            kg, ku, kd = jax.random.split(k, 3)
            return SwiGLUParams(w_gate=_linear(kg, F, D), w_up=_linear(ku, F, D), w_down=_linear(kd, D, F))

        def init_moe(k: KeyArray) -> MoEParams:
            kr, ke, ks = jax.random.split(k, 3)
            e = jax.vmap(lambda kk: init_swiglu(kk, c.expert_width))(jax.random.split(ke, c.n_experts_held))
            return MoEParams(router=_linear(kr, c.n_experts, D), router_bias=jnp.zeros((c.n_experts,)),
                             w_gate=e.w_gate, w_up=e.w_up, w_down=e.w_down,
                             shared=init_swiglu(ks, c.n_shared_experts * c.expert_width))

        k_embed, k_head, k_layers = jax.random.split(key, 3)
        layers = []
        for i, k in enumerate(jax.random.split(k_layers, c.n_layer)):
            k_att, k_mlp = jax.random.split(k)
            layers.append(LayerParams(
                norm1=jnp.ones((D,)), attn=init_mla(k_att, c.geom(c.attn_kind(i))), norm2=jnp.ones((D,)),
                mlp=init_swiglu(k_mlp, c.dense_width) if c.mlp_kind(i) == "dense" else init_moe(k_mlp),
            ))
        return Dots3Params(
            wte=jax.random.normal(k_embed, (c.vocab_size, D)) * WTE_INIT_STD,
            layers=tuple(layers), final_norm=jnp.ones((D,)),
            lm_head=jax.random.normal(k_head, (c.vocab_size, D)) / math.sqrt(D),
        )

    @staticmethod
    def cast_params(params: Dots3Params, dtype) -> Dots3Params:
        """The compute copy: matrices in `dtype`; norm gains, the index key's
        LayerNorm, the router (a near tie decided in bf16 picks another expert)
        and its bias as they are."""
        return jax.tree_util.tree_map_with_path(
            lambda path, p: p if str(getattr(path[-1], "name", path[-1])) in _F32_LEAVES
            or not jnp.issubdtype(p.dtype, jnp.floating) else p.astype(dtype),
            params,
        )

    @staticmethod
    def count_params(params: Dots3Params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    @staticmethod
    def param_specs(config, tree, mesh):
        del config, mesh  # every leaf replicated: no mesh axis is wired (check_experiment)
        return jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree)

    @staticmethod
    def flops_per_token(config: Dots3Config, seq_len: tp.Optional[int] = None, stats=None) -> float:
        """FORWARD FLOPs a token of what is computed here (this family is
        served, not trained): 2 x the parameters a token multiplies (a routed
        expert at the balanced share top_k * held / n_experts), plus, at
        context `seq_len`: a window layer's scores and values over min(window,
        T) keys; a full layer's index sweep over T / 2 keys and its scores and
        values over min(index_topk, T / 2) selected keys (expanded form)."""
        del stats
        c = config
        T = seq_len or c.block_size
        total = c.vocab_size * c.n_embd
        for i in range(c.n_layer):
            g = c.geom(c.attn_kind(i))
            total += (c.n_embd * (g.q_rank + g.latent_dim + (g.n_head if c.headwise_gate else 0))
                      + g.q_rank * g.n_head * g.qk + g.kv_rank * g.n_head * (g.nope + g.v) + g.n_head * g.v * c.n_embd)
            if g.window:
                total += g.n_head * (g.qk + g.v) * min(g.window, T)
            else:
                total += (g.q_rank * c.index_n_heads * c.index_head_dim + c.n_embd * (c.index_head_dim + c.index_n_heads)
                          + c.index_n_heads * c.index_head_dim * T / 2 + g.n_head * (g.qk + g.v) * min(c.index_topk, T / 2))
            if c.mlp_kind(i) == "dense":
                total += 3 * c.n_embd * c.dense_width
            else:
                total += c.n_experts * c.n_embd + 3 * c.n_embd * c.expert_width * (
                    c.n_shared_experts + c.moe_top_k * c.n_experts_held / c.n_experts)
        return 2.0 * total

    # ------------------------------------------------------------------
    # pieces every forward shares
    # ------------------------------------------------------------------

    @staticmethod
    def _embed(params: Dots3Params, tokens: Array) -> Array:
        with jax.named_scope("embed"):
            return jnp.take(params.wte, tokens, axis=0)

    @staticmethod
    def _ropes(c: Dots3Config) -> tp.Dict[str, tp.Tuple[Array, Array]]:
        return {kind: rope_table(c.geom(kind).rope, c.block_size, c.geom(kind).theta) for kind in (LATENT, WINDOW_LATENT)}

    @staticmethod
    def _q(c: Dots3Config, g: Geom, p: MLAParams, u: Array, rope, positions: Array) -> tp.Tuple[Array, Array, Array]:
        """u (B, T, D) -> (c_q (B, T, q_rank) rescaled, q_n (B, T, H, nope), q_r (B, T, H, rope) rotated)."""
        B, T, _ = u.shape
        with jax.named_scope("mla_q"):
            c_q = _norm(c, jnp.einsum("btd,ed->bte", u, p.w_qa), p.q_norm, c.rescale(g.q_rank))
            q = jnp.einsum("bte,fe->btf", c_q, p.w_qb).reshape(B, T, g.n_head, g.qk)
            with jax.named_scope("rope"):
                q_r = apply_rope_leading(q[..., g.nope:], *rope, positions)
            return c_q, q[..., :g.nope], q_r

    @staticmethod
    def _latent(c: Dots3Config, g: Geom, p: MLAParams, u: Array, rope, positions: Array) -> Array:
        """u (B, T, D) -> what the cache keeps of each token, (B, T, latent_dim):
        [r_kv n_kv(c_kv); k_r rotated at `positions`]."""
        with jax.named_scope("mla_kv"):
            ckv = jnp.einsum("btd,ed->bte", u, p.w_kva)
            lat = _norm(c, ckv[..., :g.kv_rank], p.kv_norm, c.rescale(g.kv_rank))
            with jax.named_scope("rope"):
                k_r = apply_rope_leading(ckv[..., None, g.kv_rank:], *rope, positions)[..., 0, :]
            return jnp.concatenate([lat, k_r], axis=-1)

    @staticmethod
    def _index_qkw(c: Dots3Config, p: IndexParams, u: Array, c_q: Array, rope, positions: Array):
        """The indexer's side of a full layer: (q^I (B, T, Hi, Ci) rotated, k^I
        (B, T, Ci) normed and rotated: what the index pool keeps, w (B, T, Hi) float32)."""
        B, T, _ = u.shape
        with jax.named_scope("dsa_proj"):
            qi = jnp.einsum("bte,fe->btf", c_q, p.w_q).reshape(B, T, c.index_n_heads, c.index_head_dim)
            ki = _layer_norm(jnp.einsum("btd,cd->btc", u, p.w_k), p.k_norm_w, p.k_norm_b)
            qi = apply_rope_leading(qi, *rope, positions)
            ki = apply_rope_leading(ki[..., None, :], *rope, positions)[..., 0, :]
            w = jnp.einsum("btd,hd->bth", u, p.w_w).astype(jnp.float32) / math.sqrt(c.index_n_heads * c.index_head_dim)
            return qi, ki, w

    @staticmethod
    def _index_scores(qi: Array, ki: Array, w: Array) -> Array:
        """I of query rows against a block of index keys: qi (..., R, Hi, Ci), ki
        (..., S, Ci), w (..., R, Hi) -> (..., R, S) float32 = sum_h w relu(q.k)."""
        s = jax.nn.relu(jnp.einsum("...rhc,...sc->...rhs", qi, ki.astype(qi.dtype), preferred_element_type=jnp.float32))
        return jnp.einsum("...rh,...rhs->...rs", w, s) + 0.0  # -0.0 made +0.0: equal scores have equal bits for every selection

    @staticmethod
    def _up(g: Geom, p: MLAParams) -> tp.Tuple[Array, Array]:
        """W_kvb as (W_uk (H, nope, r), W_uv (H, v, r))."""
        w = p.w_kvb.reshape(g.n_head, g.nope + g.v, g.kv_rank)
        return w[:, :g.nope], w[:, g.nope:]

    @staticmethod
    def _expand(g: Geom, p: MLAParams, lat: Array) -> tp.Tuple[Array, Array]:
        """Cached rows (..., S, latent_dim) -> K (..., S, H, nope + rope), V (...,
        S, H, v) of every head: the published form, k_r shared by the heads."""
        r = g.kv_rank
        kv = jnp.einsum("...sr,er->...se", lat[..., :r], p.w_kvb).reshape(*lat.shape[:-1], g.n_head, g.nope + g.v)
        k_r = jnp.broadcast_to(lat[..., None, r:], (*lat.shape[:-1], g.n_head, g.rope))
        return jnp.concatenate([kv[..., :g.nope], k_r], axis=-1), kv[..., g.nope:]

    @staticmethod
    def _absorb_q(g: Geom, p: MLAParams, q_n: Array, q_r: Array) -> Array:
        """(..., H, nope), (..., H, rope) -> the query that scores LATENT rows, (..., H, latent_dim): [q_n W_uk; q_r]."""
        with jax.named_scope("mla_q"):
            w_uk, _ = Dots3._up(g, p)
            return jnp.concatenate([jnp.einsum("...hn,hnr->...hr", q_n, w_uk).astype(q_r.dtype), q_r], axis=-1)

    @staticmethod
    def _attn_out(c: Dots3Config, g: Geom, p: MLAParams, x: Array, u: Array, o: Array, absorbed: bool) -> Array:
        """x + W_o [g_h o_h]: o (B, T, H, r) sums of latents where `absorbed`
        (W_kvb's value half is applied here), else (B, T, H, v); the gate one
        scalar a head from the layer's normed input u (B, T, D)."""
        with jax.named_scope("mla_out"):
            if absorbed:
                o = jnp.einsum("bthr,hvr->bthv", o, Dots3._up(g, p)[1])
            o = o.astype(x.dtype)
        if p.w_g is not None:
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(jnp.einsum("btd,hd->bth", u, p.w_g).astype(jnp.float32))
                o = o * gate.astype(x.dtype)[..., None]
        with jax.named_scope("mla_out"):
            return x + jnp.einsum("bte,de->btd", o.reshape(*o.shape[:2], g.n_head * g.v), p.wo)

    @staticmethod
    def _moe(c: Dots3Config, p: MoEParams, x: Array) -> tp.Tuple[Array, Array, tp.Dict[str, Array]]:
        """x (N, D) -> (the shared expert + the held experts' part of the routed layer (N, D), idx (N, k), stats)."""
        y, idx, stats = moe_serving(x, p.router, p.router_bias, p.w_gate, p.w_up, p.w_down, top_k=c.moe_top_k,
                                    scale=c.routed_scaling_factor, renormalize=c.moe_renormalize, offset=c.expert_offset)
        with jax.named_scope("moe_shared"):
            y = y + swiglu(x, p.shared.w_gate, p.shared.w_up, p.shared.w_down)
        return y, idx, stats

    @staticmethod
    def _ffn(c: Dots3Config, i: int, p: LayerParams, x: Array):
        """x (B, T, D) + FFN(n_2(x)); (x, idx | None, stats | None)."""
        with jax.named_scope("mlp"):
            h = _norm(c, x, p.norm2)
            if c.mlp_kind(i) == "dense":
                return x + swiglu(h, p.mlp.w_gate, p.mlp.w_up, p.mlp.w_down), None, None
            B, T, D = h.shape
            y, idx, stats = Dots3._moe(c, p.mlp, h.reshape(B * T, D))
            return x + y.reshape(B, T, D), idx, stats

    @staticmethod
    def _head(c: Dots3Config, params: Dots3Params, x: Array) -> Array:
        with jax.named_scope("lm_head"):
            return jnp.einsum("btd,vd->btv", _norm(c, x, params.final_norm), params.lm_head)

    # ------------------------------------------------------------------
    # the plain full forward (tests; no cache): the published, expanded form
    # ------------------------------------------------------------------

    @staticmethod
    def hidden(config: Dots3Config, params: Dots3Params, tokens: Array, *, key=None,
               inference: bool = False, attn_fn=None) -> Array:
        """Backbone forward over whole sequences (B, T) with explicit masks
        -> final-normed hidden states (B, T, D)."""
        del key, inference
        if attn_fn is not None:
            raise ValueError(f"{FAMILY}: a mesh-bound attn_fn is not wired")
        c = config
        B, T = tokens.shape
        ropes, pos = Dots3._ropes(c), jnp.arange(T)
        x = Dots3._embed(params, tokens)
        for i, p in enumerate(params.layers):
            g = c.geom(c.attn_kind(i))
            with jax.named_scope("attn"), jax.named_scope(SCOPE[g.kind]):
                u = _norm(c, x, p.norm1)
                c_q, q_n, q_r = Dots3._q(c, g, p.attn, u, ropes[g.kind], pos)
                k, v = Dots3._expand(g, p.attn, Dots3._latent(c, g, p.attn, u, ropes[g.kind], pos))
                s = jnp.einsum("bthc,bshc->bhts", jnp.concatenate([q_n, q_r], axis=-1), k).astype(jnp.float32)
                keep = visible_mask(pos[None, :], pos[:, None] + 1, g.window)  # (T, S)
                if p.attn.index is not None:
                    qi, ki, w = Dots3._index_qkw(c, p.attn.index, u, c_q, ropes[g.kind], pos)
                    with jax.named_scope("dsa_topk"):
                        bits = sortable_bits(jnp.where(keep, Dots3._index_scores(qi, ki, w), -jnp.inf)).reshape(B * T, T)
                        thr, need = kth_largest(bits, min(c.index_topk, T))
                        sel, _ = selected(bits, thr, need, jnp.zeros((B * T,), jnp.int32))
                    keep = keep & sel.reshape(B, 1, T, T)
                prob = jax.nn.softmax(jnp.where(keep, s / math.sqrt(g.qk), MASK), axis=-1).astype(v.dtype)
                x = Dots3._attn_out(c, g, p.attn, x, u, jnp.einsum("bhts,bshc->bthc", prob, v), absorbed=False)
            x, _, _ = Dots3._ffn(c, i, p, x)
        with jax.named_scope("final_norm"):
            return _norm(c, x, params.final_norm)

    @staticmethod
    def apply(config: Dots3Config, params: Dots3Params, tokens: Array) -> Array:
        """Logits (B, T, V) of whole sequences."""
        return jnp.einsum("btd,vd->btv", Dots3.hidden(config, params, tokens), params.lm_head)

    # ------------------------------------------------------------------
    # serving (sampling/serve.py reaches these through models/__init__.py)
    # ------------------------------------------------------------------

    @staticmethod
    def cache_kinds(config: Dots3Config) -> tp.Tuple[CacheKind, ...]:
        """The kinds of paged cache the layers need, the engine's first kind
        first. A stack with no layer of a kind still lists it (an empty pool)."""
        return (CacheKind(LATENT, 0, 0), CacheKind(WINDOW_LATENT, config.sliding_window, 0))

    @staticmethod
    def init_cache(config: Dots3Config, num_pages: tp.Sequence[int], page_size: int = 8,
                   dtype=jnp.bfloat16, kernel_layout: bool = False) -> ServeCache:
        """Zeroed pools of one head, `num_pages[i]` pages for kind i of
        `cache_kinds`: the latent kind's rows and index keys (two arrays under
        one page table), the window kind's rows. Counters: the expert layers'
        `(moe_counts, moe_totals)` (ops/moe.py), then `dsa`: (3, 2) uint32,
        [decoded tokens, sum of their contexts, sum of min(context,
        index_topk)] as (low, high) words."""
        c = config
        n_full, n_window = len(c.layers_of(LATENT)), len(c.layers_of(WINDOW_LATENT))
        widths = (((n_full, 1, c.geom(LATENT).latent_dim), (n_full, 1, c.index_head_dim)),
                  ((n_window, 1, c.geom(WINDOW_LATENT).latent_dim),))
        return ServeCache.zeros(FAMILY, widths, num_pages, page_size, dtype, kernel_layout,
                                (*moe_counters_init(len(c.moe_layers), c.n_experts_held), jnp.zeros((3, 2), jnp.uint32)))

    kernel_sweep_whole = True  # the window layers' is the decode program's only kernel: the full layers gather the selected rows

    @staticmethod
    def kernel_sweep(config: Dots3Config, cache: ServeCache) -> tp.Tuple[tp.Tuple[int, ...], int, int, int]:
        """(pool shape, q rows a pool head, window, sinks) of the decode
        kernel's sweep, for the engine's block counters: the WINDOW layers' (the
        only decode attention that is a kernel: the full layers gather)."""
        return cache.pools[1][0].shape, config.swa_n_head, config.sliding_window, 0

    @staticmethod
    def serve_counters(config: Dots3Config, cache: ServeCache) -> tp.Dict[str, float]:
        """The expert layers' counters (ops/moe.py `moe_serve_counters`); the
        indexer's, over decoded tokens of active slots and the full layers:
        `dsa.keys_scored` (index keys a query was scored against: its context)
        and `dsa.rows_selected` (latent rows its attention read: min(context,
        index_topk)); and what each pool array keeps of a token over its
        layers, in bytes."""
        (lat, idx), (wlat,) = cache.pools
        moe_counts, moe_totals, dsa = cache.counters
        dsa = jax.device_get(dsa).astype(object)
        tokens, keys, rows = (int(lo) + (int(hi) << 32) for lo, hi in dsa)
        n_full = len(config.layers_of(LATENT))
        per_token = lambda a: a.nbytes / (a.shape[2] * a.shape[3])
        return {**moe_serve_counters(moe_counts, moe_totals),
                "dsa.decode_tokens": tokens, "dsa.keys_scored": keys * n_full, "dsa.rows_selected": rows * n_full,
                "kv.latent_bytes_per_token": per_token(lat), "kv.index_bytes_per_token": per_token(idx),
                "kv.window_latent_bytes_per_token": per_token(wlat)}

    @staticmethod
    def _window_gather_attention(g: Geom, p: MLAParams, q: Array, pool: Array, li: int, ids: Array, col0: Array,
                                 counts: Array) -> Array:
        """XLA gather attention of a window layer in the EXPANDED form: q (B, R,
        H, nope + rope) against the pages `ids` (B, n), whose first column is
        position `col0` (B,); row r of slot b sees the last `window` of
        `counts[b, r]` keys. -> (B, R, H, v)."""
        lat = pool[li, 0, ids][..., :g.latent_dim]  # (B, n, ps, latent_dim): ONE gather whose indices carry the layer
        lat = lat.reshape(lat.shape[0], -1, g.latent_dim)
        k, v = Dots3._expand(g, p, lat.astype(q.dtype))  # (B, S, H, .)
        s = jnp.einsum("brhc,bshc->bhrs", q, k).astype(jnp.float32) / math.sqrt(g.qk)
        col = col0[:, None] + jnp.arange(lat.shape[1], dtype=jnp.int32)  # (B, S)
        keep = visible_mask(col[:, None, None, :], counts[:, None, :, None], g.window)
        prob = jax.nn.softmax(jnp.where(keep, s, MASK), axis=-1).astype(v.dtype)
        return jnp.einsum("bhrs,bshc->brhc", prob, v)

    @staticmethod
    def _index_sweep(c: Dots3Config, qi: Array, w: Array, pool: Array, li: int, table: Array, counts: Array) -> Array:
        """I of R query rows a slot against the slot's cached index keys, in
        blocks of `KEY_BLOCK` keys through the page table; the loop runs over
        the blocks that hold a visible key of some row. qi (B, R, Hi, Ci), w (B,
        R, Hi), table (B, MP), counts (B, R): row r of slot b sees `counts[b,
        r]` keys. -> (B, R, MP * ps) float32, -inf where not visible."""
        B, R = counts.shape
        ps, MP = pool.shape[3], table.shape[1]
        kp = max(1, min(MP, KEY_BLOCK // ps))  # pages a block
        blk, nb = kp * ps, -(-MP // kp)

        def body(b, scores):
            page = b * kp + jnp.arange(kp, dtype=jnp.int32)
            ids = jnp.take(table, jnp.minimum(page, MP - 1), axis=1)  # past the table: masked (col >= any count)
            ki = pool[li, 0, ids][..., :c.index_head_dim].reshape(B, blk, c.index_head_dim)
            col = b * blk + jnp.arange(blk, dtype=jnp.int32)
            s = jnp.where(col[None, None, :] < counts[:, :, None], Dots3._index_scores(qi, ki, w), -jnp.inf)
            return jax.lax.dynamic_update_slice(scores, s, (0, 0, b * blk))

        n_live = (jnp.max(counts) + blk - 1) // blk
        scores = jax.lax.fori_loop(0, n_live, body, jnp.full((B, R, nb * blk), -jnp.inf, jnp.float32))
        return scores[..., :MP * ps]

    @staticmethod
    def _select_attention(c: Dots3Config, g: Geom, q: Array, pool: Array, li: int, table: Array, pos: Array,
                          valid: Array) -> Array:
        """The absorbed attention over the SELECTED rows only: q (B, H,
        latent_dim) against the rows at positions `pos` (B, k) of each slot
        (page and offset through the table: ONE gather of k rows a slot), of
        which `valid` (B, k) count. -> (B, H, kv_rank): sums of LATENTS."""
        ps = pool.shape[3]
        rows = pool[li, 0, jnp.take_along_axis(table, pos // ps, axis=1), pos % ps]  # (B, k, lanes)
        q = jnp.pad(q.astype(rows.dtype), [(0, 0), (0, 0), (0, rows.shape[-1] - q.shape[-1])])  # the pool's padding lanes are 0
        s = jnp.einsum("bhc,bsc->bhs", q, rows).astype(jnp.float32) / math.sqrt(g.qk)
        prob = jax.nn.softmax(jnp.where(valid[:, None, :], s, MASK), axis=-1).astype(rows.dtype)
        return jnp.einsum("bhs,bsr->bhr", prob, rows[..., :g.kv_rank])

    @staticmethod
    def decode_step_paged(config: Dots3Config, params: Dots3Params, token: Array, cache: ServeCache,
                          page_table: tp.Tuple[Array, Array], lengths: Array, active: Array,
                          attn_impl: str = "auto", mesh=None, split_k: int = 1) -> tp.Tuple[Array, ServeCache]:
        """One decode step for B requests at B positions (GPT.decode_step_paged's
        contract). `page_table` is (latent table, window table), both (B, pages)
        and LOGICAL; slot b writes its token's rows at position lengths[b] in
        every pool array of the layers' kinds and attends to the index_topk
        rows its indexer selects among lengths[b] + 1 (full layers) or to the
        last `sliding_window` (window layers). Inactive slots write nothing and
        read one masked-in garbage key. `split_k` is not used: the window
        bounds the kernel's sweep at three blocks, the full layers gather.
        Returns (logits (B, V), cache)."""
        from midgpt_tpu.kernels.decode_attention import resolve_paged_impl

        del split_k
        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        tables = dict(zip((LATENT, WINDOW_LATENT), page_table))
        ps, W = cache.page_size, c.sliding_window
        B, MP = tables[WINDOW_LATENT].shape
        pos = lengths
        counts = jnp.maximum(active.astype(jnp.int32) * (pos + 1), 1)  # (B,)
        ropes = Dots3._ropes(c)
        (lat, idx), (wlat,) = cache.pools
        write_pages = {kind: jnp.where(active, jnp.take_along_axis(t, (pos // ps)[:, None], axis=1)[:, 0], pool.shape[2])
                       for (kind, t), pool in zip(tables.items(), (lat, wlat))}
        if attn_impl != "kernel":
            # the gather lowering reads the pages that [count - W, count) touches
            n_win = min(MP, -(-W // ps) + 1)
            first = jnp.minimum(jnp.maximum(counts - W, 0) // ps, MP - n_win)
            win_ids = jnp.take_along_axis(tables[WINDOW_LATENT], first[:, None] + jnp.arange(n_win, dtype=jnp.int32), axis=1)
        k_sel = min(c.index_topk, tables[LATENT].shape[1] * ps)
        moe_counts, totals, dsa = cache.counters
        x = Dots3._embed(params, token[:, None])  # (B, 1, D)
        n_moe = 0
        for i, (p, (kind, li)) in enumerate(zip(params.layers, c.pool_layers)):
            g = c.geom(kind)
            with jax.named_scope("attn"), jax.named_scope(SCOPE[kind]):
                u = _norm(c, x, p.norm1)
                c_q, q_n, q_r = Dots3._q(c, g, p.attn, u, ropes[kind], pos[:, None])
                row = Dots3._latent(c, g, p.attn, u, ropes[kind], pos[:, None])  # (B, 1, latent_dim)
                write = lambda pool, r: _write(pool, li, write_pages[kind], pos % ps, r[:, 0], attn_impl)
                if kind == LATENT:
                    qi, ki, w = Dots3._index_qkw(c, p.attn.index, u, c_q, ropes[kind], pos[:, None])
                    lat, idx = write(lat, row), write(idx, ki)
                    with jax.named_scope("dsa_index"):
                        scores = Dots3._index_sweep(c, qi, w, idx, li, tables[kind], counts[:, None])[:, 0]  # (B, S)
                    with jax.named_scope("dsa_topk"):
                        _, sel = jax.lax.top_k(scores, k_sel)  # exact; equal scores: the lower position first
                    with jax.named_scope("attn_select"):
                        o = Dots3._select_attention(c, g, Dots3._absorb_q(g, p.attn, q_n[:, 0], q_r[:, 0]), lat, li,
                                                    tables[kind], sel, sel < counts[:, None])[:, None]
                    absorbed = True
                else:
                    wlat = write(wlat, row)
                    if attn_impl == "kernel":
                        from midgpt_tpu.kernels.attention_template import paged_attention_template

                        q = Dots3._absorb_q(g, p.attn, q_n[:, 0], q_r[:, 0])  # (B, H, latent_dim)
                        o = paged_attention_template(
                            q[:, :, None, :], wlat, None, tables[kind], counts[:, None], layer=jnp.asarray(li),
                            v_lanes=g.kv_rank, sliding_window=W, scale=1.0 / math.sqrt(g.qk),
                        )  # (B, H, 1, r)
                        o, absorbed = jnp.swapaxes(o, 1, 2), True
                    else:
                        o = Dots3._window_gather_attention(g, p.attn, jnp.concatenate([q_n, q_r], axis=-1), wlat, li,
                                                           win_ids, first * ps, counts[:, None])
                        absorbed = False
                x = Dots3._attn_out(c, g, p.attn, x, u, o, absorbed)
            x, e_idx, stats = Dots3._ffn(c, i, p, x)
            if e_idx is not None:
                moe_counts, totals = moe_count_decode(moe_counts, totals, n_moe, e_idx, active, stats, offset=c.expert_offset)
                n_moe += 1
        totals = totals.at[0].add(1)
        ctx = jnp.where(active, pos + 1, 0)
        dsa = jnp.stack([_add64(dsa[0], jnp.sum(active, dtype=jnp.int32)), _add64(dsa[1], jnp.sum(ctx)),
                         _add64(dsa[2], jnp.sum(jnp.minimum(ctx, c.index_topk)))])
        logits = Dots3._head(c, params, x)[:, 0]
        return logits, ServeCache(pools=((lat, idx), (wlat,)), counters=(moe_counts, totals, dsa))

    @staticmethod
    def prefill_paged_chunk(config: Dots3Config, params: Dots3Params, tokens: Array, start: Array,
                            n_valid: Array, cache: ServeCache, page_table: tp.Tuple[Array, Array],
                            attn_impl: str = "auto", mesh=None) -> tp.Tuple[Array, ServeCache]:
        """One request's prompt chunk [start, start + n_valid) into its pages of
        both kinds (the ONE-ROW call of models/__init__.py: tokens (1, T),
        scalar start / n_valid, `page_table` the slot's (latent row, window
        row), both (1, pages)). The window row's entries behind `start -
        sliding_window` may have been freed: they are never read. Returns
        (logits of the LAST VALID row (1, 1, V), cache)."""
        from midgpt_tpu.kernels.decode_attention import resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        tables = dict(zip((LATENT, WINDOW_LATENT), page_table))
        _, T = tokens.shape
        ps, W = cache.page_size, c.sliding_window
        t_idx = jnp.arange(T, dtype=jnp.int32)
        positions = start + t_idx
        valid = t_idx < n_valid
        counts = jnp.minimum(positions, start + n_valid - 1) + 1  # pad rows see what the last valid row sees
        ropes = Dots3._ropes(c)
        (lat, idx), (wlat,) = cache.pools
        write_pages = {kind: jnp.where(valid, jnp.take(t[0], positions // ps, axis=0), pool.shape[2])
                       for (kind, t), pool in zip(tables.items(), (lat, wlat))}
        MP = tables[WINDOW_LATENT].shape[1]
        n_win = min(MP, -(-(W + T) // ps) + 1)
        first = jnp.minimum(jnp.maximum(start + 1 - W, 0) // ps, MP - n_win)
        win_ids = jax.lax.dynamic_slice_in_dim(tables[WINDOW_LATENT][0], first, n_win)[None]  # (1, n_win)
        moe_counts, totals, dsa = cache.counters
        x = Dots3._embed(params, tokens)  # (1, T, D)
        for i, (p, (kind, li)) in enumerate(zip(params.layers, c.pool_layers)):
            g = c.geom(kind)
            with jax.named_scope("attn"), jax.named_scope(SCOPE[kind]):
                u = _norm(c, x, p.norm1)
                c_q, q_n, q_r = Dots3._q(c, g, p.attn, u, ropes[kind], positions)
                rows = Dots3._latent(c, g, p.attn, u, ropes[kind], positions)  # (1, T, latent_dim)
                q = jnp.concatenate([q_n, q_r], axis=-1)  # (1, T, H, nope + rope)
                write = lambda pool, r: _write(pool, li, write_pages[kind], positions % ps, r[0], attn_impl)
                if kind == LATENT:
                    qi, ki, w = Dots3._index_qkw(c, p.attn.index, u, c_q, ropes[kind], positions)
                    lat, idx = write(lat, rows), write(idx, ki)
                    with jax.named_scope("dsa_index"):
                        scores = Dots3._index_sweep(c, qi, w, idx, li, tables[kind], counts[None])[0]  # (T, S)
                    with jax.named_scope("dsa_topk"):
                        thr, need = kth_largest(sortable_bits(scores), min(c.index_topk, scores.shape[1]))
                    with jax.named_scope("attn_select"):
                        sweep = Dots3._prefill_sparse_kernel if attn_impl == "kernel" else Dots3._prefill_sparse_sweep
                        o = sweep(g, p.attn, q[0], lat, li, tables[kind][0], counts, scores, thr, need)[None]
                else:
                    wlat = write(wlat, rows)
                    o = Dots3._window_gather_attention(g, p.attn, q, wlat, li, win_ids, (first * ps)[None], counts[None])
                x = Dots3._attn_out(c, g, p.attn, x, u, o, absorbed=False)
            x, e_idx, stats = Dots3._ffn(c, i, p, x)
            if e_idx is not None:
                totals = moe_count_dropped(totals, stats["dropped"])
        last = jax.lax.dynamic_slice_in_dim(x, jnp.maximum(n_valid - 1, 0), 1, axis=1)  # (1, 1, D)
        return Dots3._head(c, params, last), ServeCache(pools=((lat, idx), (wlat,)), counters=(moe_counts, totals, dsa))

    @staticmethod
    def _prefill_sparse_kernel(g: Geom, p: MLAParams, q: Array, pool: Array, li: int, table_row: Array, counts: Array,
                               scores: Array, thr: Array, need: Array) -> Array:
        """`_prefill_sparse_sweep` as ONE Mosaic call (kernels/latent_prefill.py),
        from the index SCORES (T, MP * ps) whose sortable bits gave `thr` and
        `need`: the slot's visible rows gathered once through the table, the
        selection AND each row's visibility folded into one int8 `keep` (T, MP
        * ps), and every block expanded, scored, masked and summed in VMEM.
        -> (T, H, v)."""
        from midgpt_tpu.kernels.latent_prefill import latent_prefill_attention, slot_rows

        keep = selection_mask(scores, thr, need) & (jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :] < counts[:, None])
        return latent_prefill_attention(
            q, slot_rows(pool, li, table_row, counts[-1]), p.w_kvb.reshape(g.n_head, g.nope + g.v, g.kv_rank), counts[-1],
            keep, nope=g.nope, scale=1.0 / math.sqrt(g.qk))

    @staticmethod
    def _prefill_sparse_sweep(g: Geom, p: MLAParams, q: Array, pool: Array, li: int, table_row: Array, counts: Array,
                              scores: Array, thr: Array, need: Array) -> Array:
        """A chunk's rows q (T, H, nope + rope) against the slot's cached
        latents (the chunk's own included: they were written first), in blocks
        of `KEY_BLOCK` keys, each block EXPANDED to K and V of every head and
        swept with an online softmax, the SELECTION AS A MASK: row t keeps
        column s iff s < counts[t] and the sortable bits of `scores[t, s]` (the
        index scores, (T, MP * ps)) lie in the row's top k (`selected` with the
        row's `thr`, `need`). The loop runs over the blocks that hold a visible
        key. -> (T, H, v). The off-TPU lowering and the tests' oracle."""
        T, H, _ = q.shape
        ps, MP = pool.shape[3], table_row.shape[0]
        kp = max(1, min(MP, KEY_BLOCK // ps))  # pages a block
        blk = kp * ps
        bits = jnp.pad(sortable_bits(scores), ((0, 0), (0, -scores.shape[1] % blk)))  # whole blocks (a table narrower than a block)
        scale = 1.0 / math.sqrt(g.qk)

        def body(b, carry):
            m, l, acc, eq_before = carry
            page = b * kp + jnp.arange(kp, dtype=jnp.int32)
            ids = jnp.take(table_row, jnp.minimum(page, MP - 1), axis=0)  # past the table: masked (col >= any count)
            rows = pool[li, 0, ids][..., :g.latent_dim].reshape(blk, g.latent_dim)
            k, v = Dots3._expand(g, p, rows.astype(q.dtype))  # (S, H, .)
            s = jnp.einsum("thc,shc->hts", q, k).astype(jnp.float32) * scale
            col = b * blk + jnp.arange(blk, dtype=jnp.int32)
            sel, eq_before = selected(jax.lax.dynamic_slice_in_dim(bits, b * blk, blk, axis=1), thr, need, eq_before)
            s = jnp.where((sel & (col[None, :] < counts[:, None]))[None], s, MASK)
            m, alpha, prob, l = online_block(m, l, s)
            pv = jnp.einsum("hts,shc->htc", prob.astype(v.dtype), v).astype(jnp.float32)
            return m, l, acc * alpha[..., None] + pv, eq_before

        init = (jnp.full((H, T), M_INIT, jnp.float32), jnp.zeros((H, T), jnp.float32),
                jnp.zeros((H, T, g.v), jnp.float32), jnp.zeros((T,), jnp.int32))
        n_live = (counts[-1] + blk - 1) // blk
        m, l, acc, _ = jax.lax.fori_loop(0, n_live, body, init)
        out, _ = finalize(m, l, acc)
        return jnp.transpose(out, (1, 0, 2))  # (T, H, v)
