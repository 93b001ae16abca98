"""Trinity (AFMoE): sliding-window layers that ROTATE beside global layers with
no position signal at all, an output gate on attention, QK-norm, four norms a
layer in sandwich position, a muP-scaled embedding, and a sigmoid-routed
mixture of 128 small experts beside one shared expert. SERVED (sample.py,
ServeEngine); training is refused by name (`check_training`: no backward is
wired).

Source: https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json
(`model_type: afmoe`, 26B-A3B: 32 layers, hidden 2,048, 32 q heads over 4 K/V
heads of 128, `sliding_attention` x3 then `full_attention`, 2 leading dense
layers, 128 experts of 1,024, top-8, one shared expert). The layers differ by
KIND, so the parameters are a tuple of per-layer pytrees and every forward is a
Python loop over them (as models/mimo_v2.py, whose two-kind cache class and XLA
attention helpers this family holds too).

RMSNorm with a gain, eps 1e-5, everywhere. A layer:

    h = x + norm_post_attn(Attn(norm_in(x)));  x' = h + norm_post_mlp(FFN(norm_pre_mlp(h)))

Embedding: x = wte[tok] * sqrt(n_embd) (`mup_enabled`). Head untied.
Attention, both kinds: q = W_q u as (32, 128), k = W_k u and v = W_v u as (4,
128), g = W_g u as (32, 128), no bias; q and k RMS-normed per head over their
128 channels with a gain (QK-norm, BEFORE any rotation); q head h reads K/V
head h // 8; scores q.k / sqrt(128), causal, plain softmax;
out = W_o (o * sigmoid(g)): the gate elementwise on the attention output.
  window (`sliding_attention`): rotate-half rotary over all 128 channels, base
    `rope_theta`; key j visible to query i iff i - sliding_window < j <= i.
  global (`full_attention`): NO rotary and no other position signal; every
    earlier key visible.
FFN: layers < `n_dense_layers` a SwiGLU of `dense_width`; the others
`ops/moe.py` (`route` is this router to the letter: sigmoid in float32 over all
`n_experts`, the top 8 of s + `expert_bias` selected, weights the selected s
renormalised, times `route_scale`) over the experts HELD here (`[expert_offset,
expert_offset + n_experts_held)`: all 128 in the benchmark's cell), plus the
shared expert, which every chip of a deployment computes alike.

Serving state is `models/mimo_v2.py`'s two kinds (models/gpt.py `ServeCache`:
`pools` = ((global K, V), (window K, V))): a `global` pool (the whole context)
and a `window` pool (the last `sliding_window` tokens; the engine frees a
window page once every future query's window has passed it), both 4 heads of
128 lanes.

Attention on the paged path:
  decode, global   kernels/attention_template.py (TPU; groups of 8 q rows a kv
                   head), or the XLA gather of the table.
  decode, window   the SAME kernel with `sliding_window`, over the window
                   kind's own logical table (reclaimed entries are never read:
                   the kernel's never-dereference rule): a block that lies
                   wholly behind the window starts no copy. At 2,048 keys a
                   gathered copy of the window's pages would be 277 MB a layer
                   a step for 64 slots; `mimo_v2` gathers because its window
                   is five pages. One partition (`split_k` 1): the window
                   bounds the sweep at three blocks whatever the context. Off
                   the TPU: the XLA gather of the pages the window touches.
  prefill chunk    XLA, `mimo_v2`'s two helpers: the window layers gather the
                   pages that [start - W, start + chunk) touches; the global
                   layers sweep the context in blocks of keys with an online
                   softmax.
"""

from __future__ import annotations

import dataclasses
import math
import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.models.gpt import CacheKind, ServeCache, _paged_write
from midgpt_tpu.models.mimo_v2 import (
    GLOBAL, WINDOW, SwiGLUParams, paged_gather_attention, prefill_sweep,
)
from midgpt_tpu.ops.attention import visible_mask
from midgpt_tpu.ops.moe import (
    moe_count_decode, moe_count_dropped, moe_counters_init, moe_prefill_rows, moe_serve_counters, moe_serving, swiglu,
)
from midgpt_tpu.ops.norms import rms_norm
from midgpt_tpu.ops.online_softmax import MASK
from midgpt_tpu.ops.rope import apply_rope_leading, rope_table
from midgpt_tpu.utils.pytree import pytree_dataclass

Array = jax.Array
KeyArray = jax.Array

FAMILY = "afmoe"
SLIDING, FULL = "sliding_attention", "full_attention"  # the published `layer_types`
# What `init` seeds the post-ATTENTION norm's gain at (every other norm gain: 1), as models/pangu_ultra.py does and
# for its reason: with random projections attention's output is close to the mean value of the context, nearly the
# same vector for every token, and normed to unit scale beside the muP-scaled embedding (unit scale too) it is half
# of every router input, common to all tokens: the seeded router then sends a chunk's pairs to a few experts. A
# trained router is balanced; at this gain the seeded one is too (PERF.md section 6 PR 46 gives both readings).
POST_ATTN_NORM_INIT = 0.1


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    """Model shape, by the published keys' meaning. The first five fields are
    named as `GPTConfig` names them (models/__init__.py)."""

    block_size: int  # serving cap on prompt + output (the source declares 131,072 positions)
    vocab_size: int
    n_layer: int  # num_hidden_layers
    n_head: int  # num_attention_heads
    n_embd: int  # hidden_size
    # per layer, as published; entries past n_layer are ignored, so a depth cut keeps the list whole
    layer_types: tp.Tuple[str, ...] = ()
    n_dense_layers: int = 2  # num_dense_layers: the leading layers whose FFN is a dense SwiGLU
    head_dim: int = 128
    n_kv_heads: int = 4  # num_key_value_heads, both kinds
    rope_theta: float = 1e4  # window layers only: a global layer does not rotate
    sliding_window: int = 2048
    dense_width: int = 6144  # intermediate_size
    n_experts: int = 128  # num_experts: the router's width
    n_experts_held: int = 128  # experts whose weights live here
    expert_offset: int = 0
    moe_top_k: int = 8  # num_experts_per_tok
    expert_width: int = 1024  # moe_intermediate_size, routed and shared experts alike
    n_shared_experts: int = 1  # num_shared_experts
    route_scale: float = 2.826
    route_norm: bool = True
    mup_enabled: bool = True  # the embedding times sqrt(hidden_size)
    rms_norm_eps: float = 1e-5
    family: str = FAMILY  # discriminates model_config in config.json

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(str(t) for t in self.layer_types))
        if self.family != FAMILY:
            raise ValueError(f"family={self.family!r} is not {FAMILY!r}")
        if len(self.layer_types) < self.n_layer or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types must name {SLIDING!r} or {FULL!r} for each of n_layer={self.n_layer} layers")
        if self.n_head % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(f"n_head={self.n_head} over {self.n_kv_heads} kv heads of {self.head_dim}")
        if not (0 <= self.expert_offset and self.expert_offset + self.n_experts_held <= self.n_experts):
            raise ValueError(
                f"experts held [{self.expert_offset}, {self.expert_offset + self.n_experts_held}) "
                f"lie outside the router's {self.n_experts}"
            )
        if not 1 <= self.moe_top_k <= self.n_experts:
            raise ValueError(f"moe_top_k={self.moe_top_k} must be in [1, n_experts={self.n_experts}]")

    # -- what the runtime reads of any model config (models/__init__.py) --
    def model(self):
        return Trinity

    def check_experiment(self, config) -> None:
        m = config.mesh
        over = {a: getattr(m, a) for a in ("fsdp", "sp", "tp", "pp", "ep") if getattr(m, a) not in (1, -1)}
        if over or config.shard_model:
            raise ValueError(
                f"{FAMILY}: no mesh axis is wired (got {over or 'shard_model=True'}): no sharding rule "
                "for the per-layer parameter tuple, no exchange of routed tokens over 'ep'"
            )
        if config.spec_layers:
            raise ValueError(f"{FAMILY}: spec_layers needs a verify step over the two-kind cache, which is not wired")

    def check_training(self, who: str) -> None:
        raise NotImplementedError(
            f"{who} cannot train a {FAMILY} model: no backward is wired for a window/global stack (the flash "
            "kernels carry no window mask, ops/moe.py's serving path is forward only, and the router's "
            "load-balance term and expert_bias update are not run). Serve it: sample.py --engine=continuous, ServeEngine."
        )

    def check_serving(self, who: str) -> None:
        """sample.py's continuous engine and ServeEngine serve this family."""

    # -- the two attention kinds, the two FFN kinds --
    def attn_kind(self, i: int) -> str:
        return WINDOW if self.layer_types[i] == SLIDING else GLOBAL

    def mlp_kind(self, i: int) -> str:
        return "dense" if i < self.n_dense_layers else "moe"

    def window_of(self, kind: str) -> int:
        """Keys a query of a layer of `kind` sees at most; 0: every earlier key."""
        return self.sliding_window if kind == WINDOW else 0

    def layers_of(self, kind: str) -> tp.Tuple[int, ...]:
        return tuple(i for i in range(self.n_layer) if self.attn_kind(i) == kind)

    @property
    def pool_layers(self) -> tp.Tuple[tp.Tuple[str, int], ...]:
        """(kind, index within that kind's pool) of every layer."""
        seen = {GLOBAL: 0, WINDOW: 0}
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
class AttnParams:
    wq: Array  # (H * C, D)
    wk: Array  # (H_kv * C, D)
    wv: Array  # (H_kv * C, D)
    wg: Array  # (H * C, D) the output gate
    wo: Array  # (D, H * C)
    q_norm: Array  # (C,) the head norms' gains
    k_norm: Array  # (C,)


@pytree_dataclass
class MoEParams:
    router: Array  # (n_experts, D)
    expert_bias: Array  # (n_experts,) the selection bias b (a buffer a balancing rule moves; seeded 0)
    w_gate: Array  # (n_experts_held, F, D)
    w_up: Array  # (n_experts_held, F, D)
    w_down: Array  # (n_experts_held, D, F)
    shared: tp.Optional[SwiGLUParams]  # the shared expert(s), n_shared_experts * F wide; None without


@pytree_dataclass
class LayerParams:
    norm_in: Array  # (D,)
    attn: AttnParams
    norm_post_attn: Array
    norm_pre_mlp: Array
    mlp: tp.Union[SwiGLUParams, MoEParams]
    norm_post_mlp: Array


@pytree_dataclass
class TrinityParams:
    wte: Array  # (V, D)
    layers: tp.Tuple[LayerParams, ...]
    final_norm: Array  # (D,)
    lm_head: Array  # (V, D), untied


_F32_LEAVES = ("norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp", "final_norm", "q_norm", "k_norm",
               "router", "expert_bias")


def _linear(key: KeyArray, out_features: int, in_features: int) -> Array:
    return jax.random.truncated_normal(key, -2.0, 2.0, (out_features, in_features)) / math.sqrt(in_features)


def _norm(c: TrinityConfig, x: Array, w: Array) -> Array:
    """Weighted RMSNorm over the trailing axis in float32 (a layer norm's (D,) gain, a head norm's (C,))."""
    return rms_norm(x.astype(jnp.float32), w.astype(jnp.float32), c.rms_norm_eps).astype(x.dtype)


def _rows_in_turn(attend: tp.Callable[..., Array], live: Array, *rows: Array) -> Array:
    """A batched prefill call's attention ROW AFTER ROW inside the program:
    `attend(*row)` for row b of every array of `rows` (leading axis B) in turn
    (`jax.lax.map`), stacked; a row that is not `live` (B,) (an empty place)
    runs nothing and gives zeros. Attention reads the POOLS and no weight, so
    nothing is lost by not batching it: each row keeps its own table row and
    its own bounds (a long slot does not lengthen a short one's sweep), and
    one row's float32 scores are live at a time (a window layer's are 170 MB a
    row at the published widths). The XLA gather of B rows' windows at once
    also lowers badly on the v5e: 25 ms a layer at B = 4 for 0.3 ms a row in
    turn (PERF.md section 6 PR 52)."""
    out = jax.eval_shape(attend, *(r[0] for r in rows))

    def one(row):
        ok, *args = row
        return jax.lax.cond(ok, lambda: attend(*args), lambda: jnp.zeros(out.shape, out.dtype))

    return jax.lax.map(one, (live, *rows))


class Trinity:
    """Namespace of pure functions over (TrinityConfig, TrinityParams)."""

    weight_decay_mask = None
    route_stats = None
    verify_step_paged = None  # no speculative verify over the two-kind cache (the engine refuses a draft)
    # the chunks of a round's prefilling slots ride one call: the engine hands every kind's (B, pages) table and
    # frees window pages per slot after it; only the attention is per slot, and runs row after row inside the program
    prefill_batched = True

    @staticmethod
    def prefill_rows(config: TrinityConfig, dense_rows: int) -> int:
        """Token rows a prefill call should carry: what brings every routed
        expert its share (ops/moe.py `moe_prefill_rows`: 2,048 at the published
        128 experts, top-8), which is past what the dense weights want."""
        return max(dense_rows, moe_prefill_rows(dense_rows, config.moe_top_k, config.n_experts))

    @staticmethod
    def init(config: TrinityConfig, key: KeyArray) -> TrinityParams:
        c = config
        D, E, E_kv = c.n_embd, c.n_head * c.head_dim, c.n_kv_heads * c.head_dim

        def init_attn(k: KeyArray) -> AttnParams:
            ks = jax.random.split(k, 5)
            return AttnParams(
                wq=_linear(ks[0], E, D), wk=_linear(ks[1], E_kv, D), wv=_linear(ks[2], E_kv, D),
                wg=_linear(ks[3], E, D), wo=_linear(ks[4], D, E),
                q_norm=jnp.ones((c.head_dim,)), k_norm=jnp.ones((c.head_dim,)),
            )

        def init_swiglu(k: KeyArray, F: int) -> SwiGLUParams:
            kg, ku, kd = jax.random.split(k, 3)
            return SwiGLUParams(w_gate=_linear(kg, F, D), w_up=_linear(ku, F, D), w_down=_linear(kd, D, F))

        def init_moe(k: KeyArray) -> MoEParams:
            kr, ks, ke = jax.random.split(k, 3)
            e = jax.vmap(lambda kk: init_swiglu(kk, c.expert_width))(jax.random.split(ke, c.n_experts_held))
            return MoEParams(
                router=_linear(kr, c.n_experts, D), expert_bias=jnp.zeros((c.n_experts,)),
                w_gate=e.w_gate, w_up=e.w_up, w_down=e.w_down,
                shared=init_swiglu(ks, c.n_shared_experts * c.expert_width) if c.n_shared_experts else None,
            )

        k_embed, k_head, k_layers = jax.random.split(key, 3)
        layers = []
        for i, k in enumerate(jax.random.split(k_layers, c.n_layer)):
            k_att, k_mlp = jax.random.split(k)
            layers.append(LayerParams(
                norm_in=jnp.ones((D,)), attn=init_attn(k_att), norm_post_attn=jnp.full((D,), POST_ATTN_NORM_INIT),
                norm_pre_mlp=jnp.ones((D,)),
                mlp=init_swiglu(k_mlp, c.dense_width) if c.mlp_kind(i) == "dense" else init_moe(k_mlp),
                norm_post_mlp=jnp.ones((D,)),
            ))
        return TrinityParams(
            wte=jax.random.normal(k_embed, (c.vocab_size, D)) / math.sqrt(D),
            layers=tuple(layers), final_norm=jnp.ones((D,)),
            lm_head=jax.random.normal(k_head, (c.vocab_size, D)) / math.sqrt(D),
        )

    @staticmethod
    def cast_params(params: TrinityParams, dtype) -> TrinityParams:
        """The compute copy: matrices in `dtype`; norm gains, the router (a near
        tie decided in bf16 picks another expert) and its bias as they are."""
        return jax.tree_util.tree_map_with_path(
            lambda path, p: p if str(getattr(path[-1], "name", path[-1])) in _F32_LEAVES
            or not jnp.issubdtype(p.dtype, jnp.floating) else p.astype(dtype),
            params,
        )

    @staticmethod
    def count_params(params: TrinityParams) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    @staticmethod
    def param_specs(config, tree, mesh):
        del config, mesh  # every leaf replicated: no mesh axis is wired (check_experiment)
        return jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree)

    @staticmethod
    def flops_per_token(config: TrinityConfig, seq_len: tp.Optional[int] = None, stats=None) -> float:
        """FORWARD FLOPs a token of what is computed here (this family is
        served, not trained): 2 x the parameters a token multiplies (a routed
        expert at the balanced share top_k * held / n_experts), plus scores and
        values over the keys a layer's kind sees at context `seq_len`."""
        del stats
        c = config
        T = seq_len or c.block_size
        E = c.n_head * c.head_dim
        total = c.vocab_size * c.n_embd
        for i in range(c.n_layer):
            window = c.window_of(c.attn_kind(i))
            total += c.n_embd * (3 * E + 2 * c.n_kv_heads * c.head_dim) + 2 * E * (min(window, T) if window else T / 2)
            if c.mlp_kind(i) == "dense":
                total += 3 * c.n_embd * c.dense_width
            else:
                routed = c.moe_top_k * c.n_experts_held / c.n_experts + c.n_shared_experts
                total += c.n_experts * c.n_embd + 3 * c.n_embd * c.expert_width * routed
        return 2.0 * total

    # ------------------------------------------------------------------
    # pieces every forward shares
    # ------------------------------------------------------------------

    @staticmethod
    def _embed(c: TrinityConfig, params: TrinityParams, tokens: Array) -> Array:
        with jax.named_scope("embed"):
            x = jnp.take(params.wte, tokens, axis=0)
            return x * math.sqrt(c.n_embd) if c.mup_enabled else x

    @staticmethod
    def _qkv(c: TrinityConfig, kind: str, p: AttnParams, u: Array, rope, positions: Array):
        """u (B, T, D) -> q (B, T, H, C), k, v (B, T, H_kv, C); q and k head-normed,
        then rotated at `positions` ((T,) or (B, T)) in a WINDOW layer only."""
        B, T, _ = u.shape
        heads = lambda w, n: jnp.einsum("btd,ed->bte", u, w).reshape(B, T, n, c.head_dim)
        q, k, v = heads(p.wq, c.n_head), heads(p.wk, c.n_kv_heads), heads(p.wv, c.n_kv_heads)
        q, k = _norm(c, q, p.q_norm), _norm(c, k, p.k_norm)
        if kind == WINDOW:
            with jax.named_scope("rope"):
                q, k = apply_rope_leading(q, *rope, positions), apply_rope_leading(k, *rope, positions)
        return q, k, v

    @staticmethod
    def _attn_out(c: TrinityConfig, p: LayerParams, x: Array, u: Array, o: Array) -> Array:
        """x + norm_post_attn(W_o (o * sigmoid(W_g u))); o (B, T, H * C)."""
        with jax.named_scope("attn_gate"):
            g = jnp.einsum("btd,ed->bte", u, p.attn.wg)
            o = o.astype(x.dtype) * jax.nn.sigmoid(g.astype(jnp.float32)).astype(x.dtype)
        return x + _norm(c, jnp.einsum("bte,de->btd", o, p.attn.wo), p.norm_post_attn)

    @staticmethod
    def _moe(c: TrinityConfig, p: MoEParams, x: Array, valid: tp.Optional[Array] = None):
        """x (N, D) -> (the shared expert + the held experts' part of the routed
        layer (N, D), idx (N, k), stats). `valid` (N,): the rows that are tokens
        (`moe_serving`); the others take no place among the experts' rows."""
        y, idx, stats = moe_serving(x, p.router, p.expert_bias, p.w_gate, p.w_up, p.w_down, top_k=c.moe_top_k,
                                    scale=c.route_scale, renormalize=c.route_norm, offset=c.expert_offset, valid=valid)
        if p.shared is not None:
            with jax.named_scope("moe_shared"):
                y = y + swiglu(x, p.shared.w_gate, p.shared.w_up, p.shared.w_down)
        return y, idx, stats

    @staticmethod
    def _ffn(c: TrinityConfig, i: int, p: LayerParams, x: Array, valid: tp.Optional[Array] = None):
        """x (B, T, D) + norm_post_mlp(FFN(norm_pre_mlp(x))); (x, idx | None, stats | None).
        `valid` (B * T,): a routed layer's rows that are tokens (`_moe`)."""
        with jax.named_scope("mlp"):
            h = _norm(c, x, p.norm_pre_mlp)
            if c.mlp_kind(i) == "dense":
                return x + _norm(c, swiglu(h, p.mlp.w_gate, p.mlp.w_up, p.mlp.w_down), p.norm_post_mlp), None, None
            B, T, D = h.shape
            y, idx, stats = Trinity._moe(c, p.mlp, h.reshape(B * T, D), valid)
            return x + _norm(c, y.reshape(B, T, D), p.norm_post_mlp), idx, stats

    @staticmethod
    def _head(c: TrinityConfig, params: TrinityParams, x: Array) -> Array:
        with jax.named_scope("lm_head"):
            return jnp.einsum("btd,vd->btv", _norm(c, x, params.final_norm), params.lm_head)

    # ------------------------------------------------------------------
    # the plain full forward (tests; no cache)
    # ------------------------------------------------------------------

    @staticmethod
    def hidden(config: TrinityConfig, params: TrinityParams, tokens: Array, *, key=None,
               inference: bool = False, attn_fn=None) -> Array:
        """Backbone forward over whole sequences (B, T) with explicit masks
        -> final-normed hidden states (B, T, D)."""
        del key, inference
        if attn_fn is not None:
            raise ValueError(f"{FAMILY}: a mesh-bound attn_fn is not wired")
        c = config
        B, T = tokens.shape
        rope = rope_table(c.head_dim, c.block_size, c.rope_theta)
        pos = jnp.arange(T)
        G = c.n_head // c.n_kv_heads
        x = Trinity._embed(c, params, tokens)
        for i, p in enumerate(params.layers):
            kind = c.attn_kind(i)
            with jax.named_scope("attn"), jax.named_scope("attn_" + kind):
                u = _norm(c, x, p.norm_in)
                q, k, v = Trinity._qkv(c, kind, p.attn, u, rope, pos)
                s = jnp.einsum("btkgc,bskc->bkgts", q.reshape(B, T, c.n_kv_heads, G, c.head_dim), k)
                s = s.astype(jnp.float32) / math.sqrt(c.head_dim)
                keep = visible_mask(pos[None, :], pos[:, None] + 1, c.window_of(kind))
                prob = jax.nn.softmax(jnp.where(keep, s, MASK), axis=-1).astype(v.dtype)
                o = jnp.einsum("bkgts,bskc->btkgc", prob, v).reshape(B, T, c.n_head * c.head_dim)
                x = Trinity._attn_out(c, p, x, u, o)
            x, _, _ = Trinity._ffn(c, i, p, x)
        with jax.named_scope("final_norm"):
            return _norm(c, x, params.final_norm)

    @staticmethod
    def apply(config: TrinityConfig, params: TrinityParams, tokens: Array) -> Array:
        """Logits (B, T, V) of whole sequences."""
        return jnp.einsum("btd,vd->btv", Trinity.hidden(config, params, tokens), params.lm_head)

    # ------------------------------------------------------------------
    # serving (sampling/serve.py reaches these through models/__init__.py)
    # ------------------------------------------------------------------

    @staticmethod
    def cache_kinds(config: TrinityConfig) -> tp.Tuple[CacheKind, ...]:
        """The kinds of paged cache the layers need, the engine's first kind
        first. A stack with no layer of a kind still lists it (an empty pool)."""
        return (CacheKind(GLOBAL, 0, 0), CacheKind(WINDOW, config.sliding_window, 0))

    @staticmethod
    def init_cache(config: TrinityConfig, num_pages: tp.Sequence[int], page_size: int = 8,
                   dtype=jnp.bfloat16, kernel_layout: bool = False) -> ServeCache:
        """Zeroed K and V pools, `num_pages[i]` pages for kind i of
        `cache_kinds`. Counters: the expert layers' `(moe_counts, moe_totals)`."""
        c = config
        k_and_v = lambda kind: ((len(c.layers_of(kind)), c.n_kv_heads, c.head_dim),) * 2
        return ServeCache.zeros(FAMILY, (k_and_v(GLOBAL), k_and_v(WINDOW)), num_pages, page_size, dtype, kernel_layout,
                                moe_counters_init(len(c.moe_layers), c.n_experts_held))

    kernel_sweep_whole = False  # the window layers run the template too, at another geometry: the sweep below is one of two

    @staticmethod
    def kernel_sweep(config: TrinityConfig, cache: ServeCache) -> tp.Tuple[tp.Tuple[int, ...], int, int, int]:
        """(pool shape, q rows a pool head, window, sinks) of the decode
        kernel's sweep, for the engine's block counters: the GLOBAL layers'
        (the window layers' grid is not counted)."""
        return cache.pools[0][0].shape, config.n_head // config.n_kv_heads, 0, 0

    @staticmethod
    def serve_counters(config: TrinityConfig, cache: ServeCache) -> tp.Dict[str, float]:
        """The expert layers' counters (ops/moe.py `moe_serve_counters`)."""
        return moe_serve_counters(*cache.counters)

    @staticmethod
    def _gather_attention(c: TrinityConfig, kind: str, q, k_pool, v_pool, li, ids, col0, counts) -> Array:
        return paged_gather_attention(q, k_pool, v_pool, li, ids, col0, counts, n_kv=c.n_kv_heads, dv=c.head_dim,
                                      window=c.window_of(kind))

    @staticmethod
    def decode_step_paged(config: TrinityConfig, params: TrinityParams, token: Array, cache: ServeCache,
                          page_table: tp.Tuple[Array, Array], lengths: Array, active: Array,
                          attn_impl: str = "auto", mesh=None, split_k: int = 1) -> tp.Tuple[Array, ServeCache]:
        """One decode step for B requests at B positions (GPT.decode_step_paged's
        contract). `page_table` is (global table, window table), both (B,
        pages) and LOGICAL (column j holds positions [j * ps, (j + 1) * ps));
        slot b writes its token's K/V at position lengths[b] in BOTH pools'
        layers and attends to lengths[b] + 1 keys (global) or the last
        `sliding_window` of them (window). Inactive slots write nothing and
        read one masked-in garbage key. Returns (logits (B, V), cache)."""
        from midgpt_tpu.kernels.attention_template import paged_attention_template
        from midgpt_tpu.kernels.decode_attention import resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        tables = dict(zip((GLOBAL, WINDOW), page_table))
        ps, W = cache.page_size, c.sliding_window
        B, MP = tables[WINDOW].shape
        pos = lengths
        counts = jnp.maximum(active.astype(jnp.int32) * (pos + 1), 1)  # (B,)
        rope = rope_table(c.head_dim, c.block_size, c.rope_theta)
        pools = dict(zip((GLOBAL, WINDOW), cache.pools))
        write_pages = {
            kind: jnp.where(active, jnp.take_along_axis(t, (pos // ps)[:, None], axis=1)[:, 0], pools[kind][0].shape[2])
            for kind, t in tables.items()
        }
        if attn_impl != "kernel":
            # the gather lowering reads the pages that [count - W, count) touches
            n_win = min(MP, -(-W // ps) + 1)
            first = jnp.minimum(jnp.maximum(counts - W, 0) // ps, MP - n_win)
            win_ids = jnp.take_along_axis(tables[WINDOW], first[:, None] + jnp.arange(n_win, dtype=jnp.int32), axis=1)
        moe_counts, totals = cache.counters
        x = Trinity._embed(c, params, token[:, None])  # (B, 1, D)
        n_moe = 0
        for i, (p, (kind, li)) in enumerate(zip(params.layers, c.pool_layers)):
            with jax.named_scope("attn"), jax.named_scope("attn_" + kind):
                u = _norm(c, x, p.norm_in)
                q, k, v = Trinity._qkv(c, kind, p.attn, u, rope, pos[:, None])
                pk, pv, _, _ = _paged_write((*pools[kind], None, None), jnp.asarray(li), write_pages[kind],
                                            pos % ps, k[:, 0], v[:, 0], attn_impl, None)
                pools[kind] = (pk, pv)
                if attn_impl == "kernel":
                    o = paged_attention_template(
                        jnp.swapaxes(q, 1, 2), pk, pv, tables[kind], counts[:, None], layer=jnp.asarray(li),
                        **(dict(sliding_window=W) if kind == WINDOW else dict(split_k=split_k)),
                    )  # (B, H, 1, C)
                    o = jnp.swapaxes(o, 1, 2).reshape(B, 1, c.n_head * c.head_dim)
                elif kind == WINDOW:
                    o = Trinity._gather_attention(c, kind, q, pk, pv, li, win_ids, first * ps, counts[:, None])
                else:
                    o = Trinity._gather_attention(c, kind, q, pk, pv, li, tables[kind], jnp.zeros_like(pos), counts[:, None])
                x = Trinity._attn_out(c, p, x, u, o)
            x, idx, stats = Trinity._ffn(c, i, p, x)
            if idx is not None:
                moe_counts, totals = moe_count_decode(moe_counts, totals, n_moe, idx, active, stats,
                                                      offset=c.expert_offset)
                n_moe += 1
        totals = totals.at[0].add(1)
        logits = Trinity._head(c, params, x)[:, 0]
        return logits, ServeCache(pools=(pools[GLOBAL], pools[WINDOW]), counters=(moe_counts, totals))

    @staticmethod
    def prefill_paged_chunk(config: TrinityConfig, params: TrinityParams, tokens: Array, start: Array,
                            n_valid: Array, cache: ServeCache, page_table: tp.Tuple[Array, Array],
                            attn_impl: str = "auto", mesh=None) -> tp.Tuple[Array, ServeCache]:
        """The prompt chunks of B slots, row b's being [start[b], start[b] +
        n_valid[b]), into their pages of both pools (GPT.prefill_paged_chunk's
        contract; `page_table` is (global table, window table), both (B,
        pages), row b the slot's). A window row's entries behind `start -
        sliding_window` may have been freed: they are never read. A row with
        n_valid 0 is an empty place: it writes nothing, attends to nothing and
        takes no row of an expert's block, and its logits mean nothing.

        Everything that reads a WEIGHT sees the B x T rows at once (the
        projections, the dense layer, the shared expert; the routed layers read
        their 128 experts once a call, and the rows past a slot's `n_valid` are
        routed nowhere: `moe_serving`'s `valid`). What reads the POOLS, the
        attention of both kinds, runs ROW AFTER ROW inside the program
        (`_rows_in_turn`): a window layer gathers the row's own window of
        pages, a global layer sweeps the row's own context (`prefill_sweep`,
        bounded by one slot's length), and an empty row runs neither.

        Returns (logits (B, V) of each row's LAST VALID position, cache): the
        engine samples from that row alone, and a 512-row chunk's logits over
        200,192 columns are 205 MB a slot. The one-row call (SCALAR `start` and
        `n_valid`, models/__init__.py) is the same program at B = 1 and hands
        back (1, 1, V)."""
        from midgpt_tpu.kernels.decode_attention import resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        one_row = jnp.ndim(start) == 0
        start, n_valid = jnp.reshape(start, (-1,)), jnp.reshape(n_valid, (-1,))
        tables = dict(zip((GLOBAL, WINDOW), page_table))
        B, T = tokens.shape
        ps, W = cache.page_size, c.sliding_window
        t_idx = jnp.arange(T, dtype=jnp.int32)
        positions = start[:, None] + t_idx  # (B, T)
        valid, live = t_idx < n_valid[:, None], n_valid > 0  # (B, T) the rows that are tokens; (B,) the places that hold a chunk
        # pad rows see what the slot's last valid row sees; an empty row, nothing
        counts = jnp.minimum(positions, (start + n_valid)[:, None] - 1) + 1
        rope = rope_table(c.head_dim, c.block_size, c.rope_theta)
        pools = dict(zip((GLOBAL, WINDOW), cache.pools))
        write_pages = {
            kind: jnp.where(valid, jnp.take_along_axis(t, positions // ps, axis=1), pools[kind][0].shape[2])
            for kind, t in tables.items()
        }
        MP = tables[WINDOW].shape[1]
        n_win = min(MP, -(-(W + T) // ps) + 1)
        first = jnp.minimum(jnp.maximum(start + 1 - W, 0) // ps, MP - n_win)  # (B,)
        win_ids = jnp.take_along_axis(tables[WINDOW], first[:, None] + jnp.arange(n_win, dtype=jnp.int32), axis=1)
        (moe_counts, totals), token_rows = cache.counters, valid.reshape(-1)
        x = Trinity._embed(c, params, tokens)  # (B, T, D)
        for i, (p, (kind, li)) in enumerate(zip(params.layers, c.pool_layers)):
            with jax.named_scope("attn"), jax.named_scope("attn_" + kind):
                u = _norm(c, x, p.norm_in)
                q, k, v = Trinity._qkv(c, kind, p.attn, u, rope, positions)
                pk, pv, _, _ = _paged_write((*pools[kind], None, None), jnp.asarray(li), write_pages[kind],
                                            positions % ps, k, v, attn_impl, None)
                pools[kind] = (pk, pv)
                if kind == WINDOW:  # the pages that [start - W, start + chunk) touches, gathered
                    attend = lambda q, ids, col0, n: Trinity._gather_attention(
                        c, kind, q[None], pk, pv, li, ids[None], col0[None], n[None])[0]
                    o = _rows_in_turn(attend, live, q, win_ids, first * ps, counts)
                else:  # the slot's whole context in blocks of keys, as far as its own length
                    attend = lambda q, row, n: prefill_sweep(q, pk, pv, li, row, n, n_kv=c.n_kv_heads, dv=c.head_dim)
                    o = _rows_in_turn(attend, live, q, tables[kind], counts)
                x = Trinity._attn_out(c, p, x, u, o)
            x, idx, stats = Trinity._ffn(c, i, p, x, token_rows)
            if idx is not None:
                totals = moe_count_dropped(totals, stats["dropped"])
        last = jnp.take_along_axis(x, jnp.maximum(n_valid - 1, 0)[:, None, None], axis=1)  # (B, 1, D)
        logits = Trinity._head(c, params, last)
        cache = ServeCache(pools=(pools[GLOBAL], pools[WINDOW]), counters=(moe_counts, totals))
        return logits if one_row else logits[:, 0], cache
