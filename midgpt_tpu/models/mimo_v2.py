"""MiMo-V2: window and global softmax-attention layers in one stack, with
different K/V-head counts, a learned sink bias in the window layers, q/k heads
of 192 beside v heads of 128, partial rotary with two bases, and a sigmoid-routed
mixture of experts without a shared expert. SERVED (sample.py, ServeEngine);
training is refused by name (`check_training`: no backward is wired).

Source: https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json
(`model_type: mimo_v2`: 48 layers, hidden 4,096, 64 q heads, 9 global : 39
window layers, 256 experts, top-8). Like `models/kimi_linear.py` the layers
differ by KIND, so the parameters are a tuple of per-layer pytrees and every
forward is a Python loop over them.

A layer, with RMSNorm carrying a weight (eps 1e-5):

    x = x + Attn(norm1(x));  x = x + FFN(norm2(x))

Attention, both kinds: q = W_q h as (64, 192), k = W_k h as (H_kv, 192), v =
0.707 * W_v h as (H_kv, 128) (`attention_value_scale`, on v: it commutes with
the weighted sum); rotate-half rotary on channels [0, 64) of each q and k head
(`partial_rotary_factor` 0.334 x 192 -> 64), the rest unrotated; q head h
reads kv head h // (64 / H_kv); scores q.k / sqrt(192), causal.
  global (`hybrid_layer_pattern` 0): H_kv = 4, rotary base 1e7, every earlier
    key visible, plain softmax.
  window (`hybrid_layer_pattern` 1): H_kv = 8, base 1e4, key j visible to
    query i iff i - 128 < j <= i, and a learned scalar s_h per q head in the
    softmax's DENOMINATOR only: p_ij = exp(a_ij) / (exp(s_h) + sum_j' exp(a_ij')).
    (Not `GPTConfig.attn_sinks`, which keeps the first tokens visible.)
FFN: layer 0 a dense SwiGLU of 16,384 (`moe_layer_freq[0]` = 0); the others
`ops/moe.py`: s = sigmoid(W_r h) in float32 over all `n_experts`, the top 8 of
s + b selected, weights the selected s renormalised, y = sum_e w_e SwiGLU_e(h)
over the experts HELD here (`[expert_offset, expert_offset + n_experts_held)`:
one chip's share of an expert-parallel deployment; the others' pairs add
nothing, and no exchange is run).

Serving state is TWO kinds of paged pool side by side (models/gpt.py
`ServeCache`: `pools` = ((global K, V), (window K, V))), because the two
kinds keep different things: a global layer one K/V entry a token for the whole
context at 4 heads, a window layer only the last `sliding_window` tokens at 8
heads. Each kind has its own pages, page table and allocator
(sampling/serve.py); the engine frees a window page once every future query's
window has passed it, in the window pool ONLY. K pages are 192 channels wide
and V pages 128 (on the kernel path 256 and 128 lanes: PagedKVCache "Layout
contract", rule 1, for each tensor).

Attention on the paged path:
  decode, global   kernels/attention_template.py (TPU; `v_dim` = 128, groups
                   of 16 q rows a kv head), or the XLA gather of the table.
  decode, window   an XLA gather of the at most ceil(W / page) + 1 pages the
                   window touches, on every backend: 128 keys are five pages,
                   and the template's grid would still step over the whole
                   table's blocks (~0.3 us each) to find them. The sink term
                   is added to the denominator here. The gathered copy grows
                   with the window (slots x pages x heads x K and V, written
                   and read again) and the grid's dead steps do not: by that
                   arithmetic the gather stops being the right choice at a
                   few hundred keys (~16 pages of 32 at 4 heads of 128 lanes).
                   `models/trinity.py` (window 2,048 = 64 pages, where a
                   gather would copy 277 MB a layer a step for 64 slots) takes
                   the template with `sliding_window`: 0.50 ms a layer call
                   for 61 live slots, 56 % of its roofline with 84 % of its
                   grid steps dead (PERF.md section 6 PR 46; the gather was
                   not measured there, nor the kernel here).
  prefill chunk    XLA: the window layers gather the pages that [start - W,
                   start + chunk) touches; the global layers sweep the context
                   in blocks of keys with an online softmax (a 512 x 16k score
                   tile a head would be 2 GB), a loop bounded by the slot's own
                   length.

Left out, in the program and in the reference alike: the three
multi-token-prediction layers and the vision and audio towers.
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

FAMILY = "mimo_v2"
GLOBAL, WINDOW = "global", "window"
PREFILL_KEY_BLOCK = 1024  # keys a step of the global layers' prefill sweep scores at once



@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    """Model shape, by the published keys' meaning. The first five fields are
    named as `GPTConfig` names them (models/__init__.py)."""

    block_size: int  # serving cap on prompt + output (the source declares 1,048,576 positions)
    vocab_size: int  # rows of wte / lm_head held here
    n_layer: int  # num_hidden_layers
    n_head: int  # num_attention_heads = swa_num_attention_heads
    n_embd: int  # hidden_size
    # per layer, as published; entries past n_layer are ignored, so a depth cut keeps the lists whole
    layer_pattern: tp.Tuple[int, ...] = ()  # hybrid_layer_pattern: 0 global, 1 window
    moe_layer_freq: tp.Tuple[int, ...] = ()  # 0 dense SwiGLU, 1 routed experts
    head_dim: int = 192  # q/k head width, global layers
    v_head_dim: int = 128
    n_kv_heads: int = 4  # num_key_value_heads (global layers)
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    swa_n_kv_heads: int = 8  # swa_num_key_value_heads
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    swa_sink_bias: bool = True  # add_swa_attention_sink_bias
    full_sink_bias: bool = False  # add_full_attention_sink_bias
    dense_width: int = 16384  # intermediate_size
    n_experts: int = 256  # n_routed_experts: the router's width
    n_experts_held: int = 256  # experts whose weights live here
    expert_offset: int = 0
    moe_top_k: int = 8  # num_experts_per_tok
    expert_width: int = 2048  # moe_intermediate_size
    routed_scaling_factor: float = 1.0  # published null
    moe_renormalize: bool = True  # norm_topk_prob
    rms_norm_eps: float = 1e-5  # layernorm_epsilon
    family: str = FAMILY  # discriminates model_config in config.json

    def __post_init__(self):
        for name in ("layer_pattern", "moe_layer_freq"):
            object.__setattr__(self, name, tuple(int(i) for i in getattr(self, name)))
        if self.family != FAMILY:
            raise ValueError(f"family={self.family!r} is not {FAMILY!r}")
        if len(self.layer_pattern) < self.n_layer or len(self.moe_layer_freq) < self.n_layer:
            raise ValueError(f"layer_pattern / moe_layer_freq name fewer than n_layer={self.n_layer} layers")
        for kind in (GLOBAL, WINDOW):
            n_kv, dq, _, _, _ = self.attn_geometry(kind)
            if self.n_head % n_kv or self.rotary_dim(dq) % 2:
                raise ValueError(f"{kind} layers: n_head={self.n_head} over {n_kv} kv heads, rotary {self.rotary_dim(dq)}")
        if not (0 <= self.expert_offset and self.expert_offset + self.n_experts_held <= self.n_experts):
            raise ValueError(
                f"experts held [{self.expert_offset}, {self.expert_offset + self.n_experts_held}) "
                f"lie outside the router's {self.n_experts}"
            )
        if not 1 <= self.moe_top_k <= self.n_experts:
            raise ValueError(f"moe_top_k={self.moe_top_k} must be in [1, n_experts={self.n_experts}]")

    # -- what the runtime reads of any model config (models/__init__.py) --
    def model(self):
        return MimoV2

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
            f"{who} cannot train a {FAMILY} model: no backward is wired for a window/global stack (the "
            "flash kernels carry no window mask and no sink term, ops/moe.py's serving path is forward "
            "only). Serve it: sample.py --engine=continuous, ServeEngine."
        )

    def check_serving(self, who: str) -> None:
        """sample.py's continuous engine and ServeEngine serve this family."""

    # -- the two attention kinds --
    def attn_kind(self, i: int) -> str:
        return WINDOW if self.layer_pattern[i] else GLOBAL

    def mlp_kind(self, i: int) -> str:
        return "moe" if self.moe_layer_freq[i] else "dense"

    def attn_geometry(self, kind: str) -> tp.Tuple[int, int, int, float, int]:
        """(kv heads, q/k head width, v head width, rotary base, window) of a kind; window 0 = none."""
        if kind == WINDOW:
            return self.swa_n_kv_heads, self.swa_head_dim, self.swa_v_head_dim, self.swa_rope_theta, self.sliding_window
        return self.n_kv_heads, self.head_dim, self.v_head_dim, self.rope_theta, 0

    def rotary_dim(self, head_dim: int) -> int:
        return int(head_dim * self.partial_rotary_factor)

    def has_sink(self, kind: str) -> bool:
        return self.swa_sink_bias if kind == WINDOW else self.full_sink_bias

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
    wq: Array  # (H * dq, D)
    wk: Array  # (H_kv * dq, D)
    wv: Array  # (H_kv * dv, D)
    wo: Array  # (D, H * dv)
    sink: tp.Optional[Array] = None  # (H,) the window layers' sink logit; None where the kind has none


@pytree_dataclass
class SwiGLUParams:
    w_gate: Array  # (F, D)
    w_up: Array  # (F, D)
    w_down: Array  # (D, F)


@pytree_dataclass
class MoEParams:
    router: Array  # (n_experts, D)
    router_bias: Array  # (n_experts,) the correction bias b: selection only
    w_gate: Array  # (n_experts_held, F, D)
    w_up: Array  # (n_experts_held, F, D)
    w_down: Array  # (n_experts_held, D, F)


@pytree_dataclass
class LayerParams:
    norm1: Array  # (D,)
    attn: AttnParams
    norm2: Array  # (D,)
    mlp: tp.Union[SwiGLUParams, MoEParams]


@pytree_dataclass
class MimoV2Params:
    wte: Array  # (V, D)
    layers: tp.Tuple[LayerParams, ...]
    final_norm: Array  # (D,)
    lm_head: Array  # (V, D), untied


_F32_LEAVES = ("norm1", "norm2", "final_norm", "router", "router_bias", "sink")


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "name", path[-1]))


def _linear(key: KeyArray, out_features: int, in_features: int) -> Array:
    return jax.random.truncated_normal(key, -2.0, 2.0, (out_features, in_features)) / math.sqrt(in_features)


def _norm(c: MimoV2Config, x: Array, w: Array) -> Array:
    return rms_norm(x.astype(jnp.float32), w.astype(jnp.float32), c.rms_norm_eps).astype(x.dtype)


def _softmax_sink(s: Array, keep: Array, sink: tp.Optional[Array]) -> Array:
    """softmax over the last axis of the f32 scores `s` where `keep`, with
    exp(sink) added to the denominator (`sink` broadcasts to s.shape[:-1])."""
    s = jnp.where(keep, s, MASK)
    m = jnp.max(s, axis=-1)
    if sink is not None:
        m = jnp.maximum(m, sink)
    p = jnp.where(keep, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    if sink is not None:
        l = l + jnp.exp(sink - m)
    return p / jnp.maximum(l, 1e-30)[..., None]


def _gather_pages(pool: Array, li: int, ids: Array, width: int) -> Array:
    """Pages `ids` (..., n) of layer `li` contiguous: (..., H, n * ps, width).
    ONE gather whose indices carry the layer (PagedKVCache "Layout contract",
    rule 3); the pool's lanes past `width` are padding."""
    g = pool[li, :, ids][..., :width]  # (..., n, H, ps, width): advanced dims lead
    g = jnp.moveaxis(g, -3, -4)  # (..., H, n, ps, width)
    return g.reshape(*g.shape[:-3], g.shape[-3] * g.shape[-2], width)


def paged_gather_attention(q: Array, k_pool: Array, v_pool: Array, li: int, ids: Array, col0: Array, counts: Array,
                           *, n_kv: int, dv: int, window: int, sink: tp.Optional[Array] = None) -> Array:
    """XLA gather attention of query rows against paged keys (every family
    with K/V pools of this layout: models/trinity.py too). q (B, R, H, dq);
    `ids` (B, n) the pages gathered, whose first column is position `col0`
    (B,); row r of slot b sees `counts[b, r]` keys, the last `window` of them
    where `window` > 0; `sink` (H,): a logit added to the softmax's denominator
    only. -> (B, R, H * dv)."""
    B, R, H, dq = q.shape
    G = H // n_kv
    kg = _gather_pages(k_pool, li, ids, dq)  # (B, n_kv, S, dq)
    vg = _gather_pages(v_pool, li, ids, dv)
    s = jnp.einsum("brkgc,bksc->bkgrs", q.reshape(B, R, n_kv, G, dq).astype(kg.dtype), kg)
    s = s.astype(jnp.float32) / math.sqrt(dq)
    col = col0[:, None] + jnp.arange(kg.shape[2], dtype=jnp.int32)  # (B, S)
    keep = visible_mask(col[:, None, None, None, :], counts[:, None, None, :, None], window)
    sink = None if sink is None else sink.astype(jnp.float32).reshape(n_kv, G)[None, :, :, None]
    prob = _softmax_sink(s, keep, sink).astype(vg.dtype)
    return jnp.einsum("bkgrs,bksc->brkgc", prob, vg).reshape(B, R, H * dv)


def prefill_sweep(q: Array, k_pool: Array, v_pool: Array, li: int, table_row: Array, counts: Array,
                  *, n_kv: int, dv: int, sink: tp.Optional[Array] = None) -> Array:
    """A chunk's rows q (T, H, dq) against the slot's whole context, in
    blocks of `PREFILL_KEY_BLOCK` keys with an online softmax; the loop runs
    over the blocks that hold a visible key, not over the table. Row t sees
    `counts[t]` keys. -> (T, H * dv)."""
    T, H, dq = q.shape
    G, ps, MP = H // n_kv, k_pool.shape[3], table_row.shape[0]
    kp = max(1, min(MP, PREFILL_KEY_BLOCK // ps))  # pages a block
    qg = q.reshape(T, n_kv, G, dq)

    def body(b, carry):
        m, l, acc = carry
        page = b * kp + jnp.arange(kp, dtype=jnp.int32)
        ids = jnp.take(table_row, jnp.minimum(page, MP - 1), axis=0)  # past the table: masked (col >= any count)
        kg = _gather_pages(k_pool, li, ids, dq)  # (n_kv, kp * ps, dq)
        vg = _gather_pages(v_pool, li, ids, dv)
        s = jnp.einsum("tkgc,ksc->kgts", qg.astype(kg.dtype), kg).astype(jnp.float32) / math.sqrt(dq)
        col = b * (kp * ps) + jnp.arange(kp * ps, dtype=jnp.int32)
        s = jnp.where(col[None, None, None, :] < counts[None, None, :, None], s, MASK)
        m, alpha, prob, l = online_block(m, l, s)
        pv = jnp.einsum("kgts,ksc->kgtc", prob.astype(vg.dtype), vg).astype(jnp.float32)
        return m, l, acc * alpha[..., None] + pv

    init = (jnp.full((n_kv, G, T), M_INIT, jnp.float32), jnp.zeros((n_kv, G, T), jnp.float32),
            jnp.zeros((n_kv, G, T, dv), jnp.float32))
    n_live = (counts[-1] + kp * ps - 1) // (kp * ps)
    m, l, acc = jax.lax.fori_loop(0, n_live, body, init)
    if sink is not None:  # a global kind with a sink bias: one more term in the denominator
        sink = sink.astype(jnp.float32).reshape(n_kv, G)[:, :, None]
        m_new = jnp.maximum(m, sink)
        l, acc, m = l * jnp.exp(m - m_new) + jnp.exp(sink - m_new), acc * jnp.exp(m - m_new)[..., None], m_new
    out, _ = finalize(m, l, acc)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(T, H * dv)


class MimoV2:
    """Namespace of pure functions over (MimoV2Config, MimoV2Params)."""

    weight_decay_mask = None
    route_stats = None
    verify_step_paged = None  # no speculative verify over the two-kind cache (the engine refuses a draft)

    @staticmethod
    def init(config: MimoV2Config, key: KeyArray) -> MimoV2Params:
        c = config
        D, H = c.n_embd, c.n_head

        def init_attn(k: KeyArray, kind: str) -> AttnParams:
            n_kv, dq, dv, _, _ = c.attn_geometry(kind)
            ks = jax.random.split(k, 5)
            return AttnParams(
                wq=_linear(ks[0], H * dq, D), wk=_linear(ks[1], n_kv * dq, D),
                wv=_linear(ks[2], n_kv * dv, D), wo=_linear(ks[3], D, H * dv),
                # seeded at unit scale so that the sink term is not negligible beside the scores
                sink=jax.random.normal(ks[4], (H,)) if c.has_sink(kind) else None,
            )

        def init_swiglu(k: KeyArray, F: int) -> SwiGLUParams:
            kg, ku, kd = jax.random.split(k, 3)
            return SwiGLUParams(w_gate=_linear(kg, F, D), w_up=_linear(ku, F, D), w_down=_linear(kd, D, F))

        def init_moe(k: KeyArray) -> MoEParams:
            kr, kb, ke = jax.random.split(k, 3)
            e = jax.vmap(lambda kk: init_swiglu(kk, c.expert_width))(jax.random.split(ke, c.n_experts_held))
            return MoEParams(
                router=_linear(kr, c.n_experts, D),
                router_bias=0.01 * jax.random.normal(kb, (c.n_experts,)),  # as models/kimi_linear.py seeds it
                w_gate=e.w_gate, w_up=e.w_up, w_down=e.w_down,
            )

        k_embed, k_head, k_layers = jax.random.split(key, 3)
        layers = []
        for i, k in enumerate(jax.random.split(k_layers, c.n_layer)):
            k_att, k_mlp = jax.random.split(k)
            layers.append(LayerParams(
                norm1=jnp.ones((D,)), attn=init_attn(k_att, c.attn_kind(i)), norm2=jnp.ones((D,)),
                mlp=init_swiglu(k_mlp, c.dense_width) if c.mlp_kind(i) == "dense" else init_moe(k_mlp),
            ))
        return MimoV2Params(
            wte=jax.random.normal(k_embed, (c.vocab_size, D)) / math.sqrt(D),
            layers=tuple(layers), final_norm=jnp.ones((D,)),
            lm_head=jax.random.normal(k_head, (c.vocab_size, D)) / math.sqrt(D),
        )

    @staticmethod
    def cast_params(params: MimoV2Params, dtype) -> MimoV2Params:
        """The compute copy: matrices in `dtype`; norm weights, the router (a
        near tie decided in bf16 picks another expert) and the sink logits as they are."""
        return jax.tree_util.tree_map_with_path(
            lambda path, p: p if _leaf_name(path) in _F32_LEAVES or not jnp.issubdtype(p.dtype, jnp.floating)
            else p.astype(dtype),
            params,
        )

    @staticmethod
    def count_params(params: MimoV2Params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    @staticmethod
    def param_specs(config, tree, mesh):
        del config, mesh  # every leaf replicated: no mesh axis is wired (check_experiment)
        return jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree)

    @staticmethod
    def flops_per_token(config: MimoV2Config, seq_len: tp.Optional[int] = None, stats=None) -> float:
        """FORWARD FLOPs a token of what is computed here (this family is
        served, not trained): 2 x the parameters a token multiplies (a routed
        expert at the balanced share top_k * held / n_experts), plus scores and
        values over the keys a layer's kind sees at context `seq_len`."""
        del stats
        c = config
        T = seq_len or c.block_size
        total = c.vocab_size * c.n_embd
        for i in range(c.n_layer):
            n_kv, dq, dv, _, window = c.attn_geometry(c.attn_kind(i))
            total += c.n_embd * (c.n_head * dq + n_kv * (dq + dv) + c.n_head * dv)
            total += c.n_head * (dq + dv) * (min(window, T) if window else T / 2)
            if c.mlp_kind(i) == "dense":
                total += 3 * c.n_embd * c.dense_width
            else:
                total += c.n_experts * c.n_embd + 3 * c.n_embd * c.expert_width * c.moe_top_k * c.n_experts_held / c.n_experts
        return 2.0 * total

    # ------------------------------------------------------------------
    # pieces every forward shares
    # ------------------------------------------------------------------

    @staticmethod
    def _rope_tables(c: MimoV2Config) -> tp.Dict[str, tp.Tuple[Array, Array]]:
        # in one fixed order: a set's order follows the process's hash seed, and the
        # order of the traced operations with it (another program text, a compile-cache miss)
        kinds = [k for k in (GLOBAL, WINDOW) if c.layers_of(k)]
        return {k: rope_table(c.rotary_dim(c.attn_geometry(k)[1]), c.block_size, c.attn_geometry(k)[3]) for k in kinds}

    @staticmethod
    def _qkv(c: MimoV2Config, kind: str, p: AttnParams, h: Array, rope, positions: Array):
        """h (B, T, D) -> q (B, T, H, dq), k (B, T, H_kv, dq) both rotated at
        `positions` ((T,) or (B, T)), v (B, T, H_kv, dv) scaled."""
        B, T, _ = h.shape
        n_kv, dq, dv, _, _ = c.attn_geometry(kind)
        q = jnp.einsum("btd,ed->bte", h, p.wq).reshape(B, T, c.n_head, dq)
        k = jnp.einsum("btd,ed->bte", h, p.wk).reshape(B, T, n_kv, dq)
        v = jnp.einsum("btd,ed->bte", h, p.wv).reshape(B, T, n_kv, dv)
        sin, cos = rope
        q, k = apply_rope_leading(q, sin, cos, positions), apply_rope_leading(k, sin, cos, positions)
        return q, k, (v * c.attention_value_scale).astype(v.dtype)

    @staticmethod
    def _moe(c: MimoV2Config, p: MoEParams, x: Array) -> tp.Tuple[Array, Array, tp.Dict[str, Array]]:
        """x (N, D) -> (the held experts' part of the layer (N, D), idx (N, k), stats)."""
        return moe_serving(x, p.router, p.router_bias, p.w_gate, p.w_up, p.w_down, top_k=c.moe_top_k,
                           scale=c.routed_scaling_factor, renormalize=c.moe_renormalize, offset=c.expert_offset)

    @staticmethod
    def _ffn(c: MimoV2Config, i: int, p: LayerParams, x: Array):
        """x (B, T, D) + FFN(norm2(x)); (x, idx | None, stats | None)."""
        with jax.named_scope("mlp"):
            h = _norm(c, x, p.norm2)
            if c.mlp_kind(i) == "dense":
                return x + swiglu(h, p.mlp.w_gate, p.mlp.w_up, p.mlp.w_down), None, None
            B, T, D = h.shape
            y, idx, stats = MimoV2._moe(c, p.mlp, h.reshape(B * T, D))
            return x + y.reshape(B, T, D), idx, stats

    @staticmethod
    def _head(c: MimoV2Config, params: MimoV2Params, x: Array) -> Array:
        with jax.named_scope("final_norm"):
            x = _norm(c, x, params.final_norm)
        return jnp.einsum("btd,vd->btv", x, params.lm_head)

    # ------------------------------------------------------------------
    # the plain full forward (tests; no cache)
    # ------------------------------------------------------------------

    @staticmethod
    def hidden(config: MimoV2Config, params: MimoV2Params, tokens: Array, *, key=None,
               inference: bool = False, attn_fn=None) -> Array:
        """Backbone forward over whole sequences (B, T) with explicit masks
        -> final-normed hidden states (B, T, D)."""
        del key, inference
        if attn_fn is not None:
            raise ValueError(f"{FAMILY}: a mesh-bound attn_fn is not wired")
        c = config
        B, T = tokens.shape
        ropes = MimoV2._rope_tables(c)
        pos = jnp.arange(T)
        with jax.named_scope("embed"):
            x = jnp.take(params.wte, tokens, axis=0)
        for i, p in enumerate(params.layers):
            kind = c.attn_kind(i)
            n_kv, dq, dv, _, window = c.attn_geometry(kind)
            G = c.n_head // n_kv
            with jax.named_scope("attn"), jax.named_scope("attn_" + kind):
                q, k, v = MimoV2._qkv(c, kind, p.attn, _norm(c, x, p.norm1), ropes[kind], pos)
                s = jnp.einsum("btkgc,bskc->bkgts", q.reshape(B, T, n_kv, G, dq), k).astype(jnp.float32) / math.sqrt(dq)
                keep = visible_mask(pos[None, :], pos[:, None] + 1, window)
                sink = None if p.attn.sink is None else p.attn.sink.astype(jnp.float32).reshape(n_kv, G)[None, :, :, None]
                prob = _softmax_sink(s, keep, sink).astype(v.dtype)
                o = jnp.einsum("bkgts,bskc->btkgc", prob, v).reshape(B, T, c.n_head * dv)
                x = x + jnp.einsum("bte,de->btd", o, p.attn.wo)
            x, _, _ = MimoV2._ffn(c, i, p, x)
        with jax.named_scope("final_norm"):
            return _norm(c, x, params.final_norm)

    @staticmethod
    def apply(config: MimoV2Config, params: MimoV2Params, tokens: Array) -> Array:
        """Logits (B, T, V) of whole sequences."""
        return jnp.einsum("btd,vd->btv", MimoV2.hidden(config, params, tokens), params.lm_head)

    # ------------------------------------------------------------------
    # serving (sampling/serve.py reaches these through models/__init__.py)
    # ------------------------------------------------------------------

    @staticmethod
    def cache_kinds(config: MimoV2Config) -> tp.Tuple[CacheKind, ...]:
        """The kinds of paged cache the layers need, the engine's first kind
        first. A stack with no layer of a kind still lists it (an empty pool)."""
        return (CacheKind(GLOBAL, 0, 0), CacheKind(WINDOW, config.sliding_window, 0))

    @staticmethod
    def init_cache(config: MimoV2Config, num_pages: tp.Sequence[int], page_size: int = 8,
                   dtype=jnp.bfloat16, kernel_layout: bool = False) -> ServeCache:
        """Zeroed pools, `num_pages[i]` pages for kind i of `cache_kinds`: K at
        the kind's q/k width and V at its v width. Counters: the expert layers'
        `(moe_counts, moe_totals)` (ops/moe.py)."""
        c = config

        def k_and_v(kind: str):
            (n_kv, dq, dv, _, _), layers = c.attn_geometry(kind), len(c.layers_of(kind))
            return (layers, n_kv, dq), (layers, n_kv, dv)

        return ServeCache.zeros(FAMILY, (k_and_v(GLOBAL), k_and_v(WINDOW)), num_pages, page_size, dtype, kernel_layout,
                                moe_counters_init(len(c.moe_layers), c.n_experts_held))

    kernel_sweep_whole = True  # the global layers' is the decode program's only kernel: the window layers gather

    @staticmethod
    def kernel_sweep(config: MimoV2Config, cache: ServeCache) -> tp.Tuple[tp.Tuple[int, ...], int, int, int]:
        """(pool shape, q rows a pool head, window, sinks) of the decode
        kernel's sweep, for the engine's block counters: the global layers'."""
        return cache.pools[0][0].shape, config.n_head // config.n_kv_heads, 0, 0

    @staticmethod
    def serve_counters(config: MimoV2Config, cache: ServeCache) -> tp.Dict[str, float]:
        """The expert layers' counters (ops/moe.py `moe_serve_counters`)."""
        return moe_serve_counters(*cache.counters)

    @staticmethod
    def _paged_attention(c: MimoV2Config, kind: str, p: AttnParams, q: Array, k_pool: Array, v_pool: Array,
                         li: int, ids: Array, col0: Array, counts: Array) -> Array:
        """`paged_gather_attention` at the layer kind's geometry (the window and the sink term by the kind)."""
        n_kv, _, dv, _, window = c.attn_geometry(kind)
        return paged_gather_attention(q, k_pool, v_pool, li, ids, col0, counts, n_kv=n_kv, dv=dv, window=window, sink=p.sink)

    @staticmethod
    def decode_step_paged(config: MimoV2Config, params: MimoV2Params, token: Array, cache: ServeCache,
                          page_table: tp.Tuple[Array, Array], lengths: Array, active: Array,
                          attn_impl: str = "auto", mesh=None, split_k: int = 1) -> tp.Tuple[Array, ServeCache]:
        """One decode step for B requests at B positions (GPT.decode_step_paged's
        contract). `page_table` is (global table, window table), both (B,
        pages); slot b writes its token's K/V at position lengths[b] in BOTH
        pools' layers and attends to lengths[b] + 1 keys (global) or the last
        `sliding_window` of them (window). Inactive slots write nothing and
        read one masked-in garbage key. Returns (logits (B, V), cache)."""
        from midgpt_tpu.kernels.decode_attention import resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        tables = dict(zip((GLOBAL, WINDOW), page_table))
        ps = cache.page_size
        pos = lengths
        counts = jnp.maximum(active.astype(jnp.int32) * (pos + 1), 1)  # (B,)
        ropes = MimoV2._rope_tables(c)
        pools = dict(zip((GLOBAL, WINDOW), cache.pools))
        write_pages = {
            kind: jnp.where(active, jnp.take_along_axis(t, (pos // ps)[:, None], axis=1)[:, 0], pools[kind][0].shape[2])
            for kind, t in tables.items()
        }
        # the window layers read the pages that [count - W, count) touches
        W = c.sliding_window
        n_win = min(tables[WINDOW].shape[1], -(-W // ps) + 1)
        first = jnp.minimum(jnp.maximum(counts - W, 0) // ps, tables[WINDOW].shape[1] - n_win)
        win_ids = jnp.take_along_axis(tables[WINDOW], first[:, None] + jnp.arange(n_win, dtype=jnp.int32), axis=1)
        moe_counts, totals = cache.counters
        with jax.named_scope("embed"):
            x = jnp.take(params.wte, token[:, None], axis=0)  # (B, 1, D)
        n_moe = 0
        for i, (p, (kind, li)) in enumerate(zip(params.layers, c.pool_layers)):
            _, _, dv, _, _ = c.attn_geometry(kind)
            with jax.named_scope("attn"), jax.named_scope("attn_" + kind):
                q, k, v = MimoV2._qkv(c, kind, p.attn, _norm(c, x, p.norm1), ropes[kind], pos[:, None])
                pk, pv, _, _ = _paged_write((*pools[kind], None, None), jnp.asarray(li), write_pages[kind],
                                            pos % ps, k[:, 0], v[:, 0], attn_impl, None)
                pools[kind] = (pk, pv)
                if kind == WINDOW:
                    o = MimoV2._paged_attention(c, kind, p.attn, q, pk, pv, li, win_ids, first * ps, counts[:, None])
                elif attn_impl == "kernel" and p.attn.sink is None:  # the template has no sink term
                    from midgpt_tpu.kernels.attention_template import paged_attention_template

                    o = paged_attention_template(
                        jnp.swapaxes(q, 1, 2), pk, pv, tables[kind], counts[:, None],
                        split_k=split_k, layer=jnp.asarray(li), v_dim=dv,
                    )  # (B, H, 1, dv)
                    o = jnp.swapaxes(o, 1, 2).reshape(q.shape[0], 1, c.n_head * dv)
                else:
                    o = MimoV2._paged_attention(c, kind, p.attn, q, pk, pv, li, tables[kind],
                                                jnp.zeros_like(pos), counts[:, None])
                x = x + jnp.einsum("bte,de->btd", o.astype(x.dtype), p.attn.wo)
            x, idx, stats = MimoV2._ffn(c, i, p, x)
            if idx is not None:
                moe_counts, totals = moe_count_decode(moe_counts, totals, n_moe, idx, active, stats,
                                                      offset=c.expert_offset)
                n_moe += 1
        totals = totals.at[0].add(1)
        logits = MimoV2._head(c, params, x)[:, 0]
        return logits, ServeCache(pools=(pools[GLOBAL], pools[WINDOW]), counters=(moe_counts, totals))

    # one row a call: two page tables a slot, window pages freed per slot
    prefill_batched = False

    @staticmethod
    def prefill_paged_chunk(config: MimoV2Config, params: MimoV2Params, tokens: Array, start: Array,
                            n_valid: Array, cache: ServeCache, page_table: tp.Tuple[Array, Array],
                            attn_impl: str = "auto", mesh=None) -> tp.Tuple[Array, ServeCache]:
        """One request's prompt chunk [start, start + n_valid) into its pages
        of both pools (GPT.prefill_paged_chunk's contract; `page_table` is the
        slot's (global row, window row), both (1, pages)). The window row's
        entries behind `start - sliding_window` may have been freed: they are
        never read. Returns (logits of the LAST VALID row (1, 1, V), cache):
        the engine samples from that row alone, and a 512-row chunk's logits
        are 20 MB it would otherwise copy to the host."""
        from midgpt_tpu.kernels.decode_attention import resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        tables = dict(zip((GLOBAL, WINDOW), page_table))
        _, T = tokens.shape
        ps = cache.page_size
        t_idx = jnp.arange(T, dtype=jnp.int32)
        positions = start + t_idx
        valid = t_idx < n_valid
        counts = jnp.minimum(positions, start + n_valid - 1) + 1  # pad rows see what the last valid row sees
        ropes = MimoV2._rope_tables(c)
        pools = dict(zip((GLOBAL, WINDOW), cache.pools))
        write_pages = {
            kind: jnp.where(valid, jnp.take(t[0], positions // ps, axis=0), pools[kind][0].shape[2])
            for kind, t in tables.items()
        }
        W = c.sliding_window
        n_win = min(tables[WINDOW].shape[1], -(-(W + T) // ps) + 1)
        first = jnp.minimum(jnp.maximum(start + 1 - W, 0) // ps, tables[WINDOW].shape[1] - n_win)
        win_ids = jax.lax.dynamic_slice_in_dim(tables[WINDOW][0], first, n_win)[None]  # (1, n_win)
        moe_counts, totals = cache.counters
        with jax.named_scope("embed"):
            x = jnp.take(params.wte, tokens, axis=0)  # (1, T, D)
        for i, (p, (kind, li)) in enumerate(zip(params.layers, c.pool_layers)):
            with jax.named_scope("attn"), jax.named_scope("attn_" + kind):
                q, k, v = MimoV2._qkv(c, kind, p.attn, _norm(c, x, p.norm1), ropes[kind], positions)
                pk, pv, _, _ = _paged_write((*pools[kind], None, None), jnp.asarray(li), write_pages[kind],
                                            positions % ps, k[0], v[0], attn_impl, None)
                pools[kind] = (pk, pv)
                if kind == WINDOW:
                    o = MimoV2._paged_attention(c, kind, p.attn, q, pk, pv, li, win_ids, (first * ps)[None], counts[None])
                else:
                    o = MimoV2._prefill_sweep(c, kind, p.attn, q[0], pk, pv, li, tables[kind][0], counts)[None]
                x = x + jnp.einsum("bte,de->btd", o.astype(x.dtype), p.attn.wo)
            x, idx, stats = MimoV2._ffn(c, i, p, x)
            if idx is not None:
                totals = moe_count_dropped(totals, stats["dropped"])
        last = jax.lax.dynamic_slice_in_dim(x, jnp.maximum(n_valid - 1, 0), 1, axis=1)  # (1, 1, D)
        logits = MimoV2._head(c, params, last)
        return logits, ServeCache(pools=(pools[GLOBAL], pools[WINDOW]), counters=(moe_counts, totals))

    @staticmethod
    def _prefill_sweep(c: MimoV2Config, kind: str, p: AttnParams, q: Array, k_pool: Array, v_pool: Array,
                       li: int, table_row: Array, counts: Array) -> Array:
        """`prefill_sweep` at the layer kind's geometry."""
        n_kv, _, dv, _, _ = c.attn_geometry(kind)
        return prefill_sweep(q, k_pool, v_pool, li, table_row, counts, n_kv=n_kv, dv=dv, sink=p.sink)
