"""openPangu-Ultra-MoE: latent attention (MLA) with a low-rank query and ONE
rotated key channel group shared by all heads, sandwich norms (a sublayer's
OUTPUT is normed before it is added: four norms a layer), and a sigmoid-routed
mixture of experts beside a shared expert. SERVED (sample.py, ServeEngine) from
a LATENT paged cache; training is refused by name (`check_training`).

Source: https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json
(`model_type: pangu_ultra_moe`: 61 layers, hidden 7,680, 128 heads, q_lora_rank
1,536, kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128, rope_theta 25.6e6, a
dense MLP of 18,432 in the first 3 layers, then 256 routed experts of 2,048,
top-8, one shared expert, vocabulary 153,600, untied head). The layers differ
by KIND of MLP, so the parameters are a tuple of per-layer pytrees and every
forward is a Python loop over them (as models/mimo_v2.py).

A layer, with n(.) an RMSNorm carrying a weight (eps 1e-5):

    x = x + n_post_attn(MLA(n_in(x)));  x = x + n_post_mlp(F(n_pre_mlp(x)))

F: the SwiGLU MLP in a dense layer; `shared(h) + sum_k w_k expert_k(h)` in an
expert layer, s = sigmoid(W_r h) in float32, the 8 largest of 256, w = 2.5 *
s_sel / sum(s_sel), no groups and no correction bias (`ops/moe.py` `route`
with a zero bias), over the experts HELD here (`[expert_offset, expert_offset +
n_experts_held)`: one chip's share; the others' pairs add nothing, no exchange
is run; the shared expert is whole on every chip).

MLA: c_q = n_q(W_qa h) (1,536); q = W_qb c_q -> 128 heads of [q_n (128); q_r
(64)]; [c_kv; k_r] = W_kva h (512 + 64); c = n_kv(c_kv); rotate-half rotary on
q_r and on the ONE k_r; [k_n; v] = W_kvb c a head (128 + 128); scores (q_n.k_n
+ q_r.k_r) / sqrt(192), causal softmax, values v, W_o.

WHAT IS CACHED is the LATENT, once: a token's row of a layer is [c (512); k_r
rotated (64)] = 576 values (models/gpt.py `ServeCache`: `pools` = ((rows,),), one
array of one head; 640 lanes on the kernel path, 1,280 B in bf16), not K and V of 128 heads (81,920 B). K is
the whole row and V is a VIEW of its leading 512 lanes.

Attention on the paged path:
  decode         ABSORBED: W_kvb's key half is folded into the query (q' = q_n
                 W_uk: 128 -> 512 a head) and its value half into the output
                 (o = (sum p c) W_uv), so a step scores 128 query rows against
                 one pool head of 576 channels and reads the values as the
                 same page's leading 512 lanes: kernels/attention_template.py
                 with `v_lanes` (TPU), or the XLA gather of the same
                 arithmetic.
  prefill chunk  XLA over blocks of `PREFILL_KEY_BLOCK` cached latents with an
                 online softmax, a loop bounded by the slot's own length; a
                 block's latents are EXPANDED to K and V of every head there
                 (the published form: at 512 query rows the expansion is
                 cheaper than scoring against 576 + 512 channels a head).

Left out, in the program and in the reference alike: the next-token-prediction
layer (`num_nextn_predict_layers` 1), which a deployment that does not
speculate leaves unloaded.
"""

from __future__ import annotations

import dataclasses
import math
import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.models.gpt import CacheKind, ServeCache, _paged_write
from midgpt_tpu.ops.moe import (
    moe_count_decode, moe_count_dropped, moe_counters_init, moe_serve_counters, moe_serving, swiglu,
)
from midgpt_tpu.ops.norms import rms_norm
from midgpt_tpu.ops.online_softmax import M_INIT, MASK, finalize, online_block
from midgpt_tpu.ops.rope import apply_rope_leading, rope_table
from midgpt_tpu.utils.pytree import pytree_dataclass

Array = jax.Array
KeyArray = jax.Array

FAMILY = "pangu_ultra"
LATENT = "latent"
PREFILL_KEY_BLOCK = 1024  # cached latents a step of the prefill sweep expands and scores at once
# What `init` seeds the post-ATTENTION norm's gain at (every other norm weight: 1). With random projections
# attention's output is close to the mean value of the context, nearly the same vector for every token; normed to
# unit scale beside a token embedding of 1 / sqrt(D) it makes a third to a half of every router input common to
# all tokens, and the seeded router's selection collapses onto a dozen experts (200-400 of a chunk's 4,096 pairs on
# one expert against a mean of 16), so a chip's held experts see 0.65-1.45 times their share by the seed. A trained
# router is balanced; at this gain the seeded one is too (PERF.md section 6 PR 39).
POST_ATTN_NORM_INIT = 0.1


@dataclasses.dataclass(frozen=True)
class PanguUltraConfig:
    """Model shape, by the published keys' meaning. The first five fields are
    named as `GPTConfig` names them (models/__init__.py)."""

    block_size: int  # serving cap on prompt + output (the source declares 131,072 positions)
    vocab_size: int  # rows of wte / lm_head held here
    n_layer: int  # num_hidden_layers
    n_head: int  # num_attention_heads
    n_embd: int  # hidden_size
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25.6e6
    dense_width: int = 18432  # intermediate_size
    first_k_dense: int = 3  # first_k_dense_replace: leading layers with a dense MLP
    n_experts: int = 256  # n_routed_experts: the router's width
    n_experts_held: int = 256  # experts whose weights live here
    expert_offset: int = 0
    moe_top_k: int = 8  # num_experts_per_tok
    expert_width: int = 2048  # moe_intermediate_size (routed and shared)
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    moe_renormalize: bool = True  # norm_topk_prob
    sandwich_norm: bool = True  # a sublayer's output is normed before the residual add
    rms_norm_eps: float = 1e-5
    family: str = FAMILY  # discriminates model_config in config.json

    def __post_init__(self):
        if self.family != FAMILY:
            raise ValueError(f"family={self.family!r} is not {FAMILY!r}")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim={self.qk_rope_head_dim}: rotate-half needs an even width")
        if not (0 <= self.expert_offset and self.expert_offset + self.n_experts_held <= self.n_experts):
            raise ValueError(
                f"experts held [{self.expert_offset}, {self.expert_offset + self.n_experts_held}) "
                f"lie outside the router's {self.n_experts}"
            )
        if not 1 <= self.moe_top_k <= self.n_experts:
            raise ValueError(f"moe_top_k={self.moe_top_k} must be in [1, n_experts={self.n_experts}]")

    # -- what the runtime reads of any model config (models/__init__.py) --
    def model(self):
        return PanguUltra

    def check_experiment(self, config) -> None:
        m = config.mesh
        over = {a: getattr(m, a) for a in ("fsdp", "sp", "tp", "pp", "ep") if getattr(m, a) not in (1, -1)}
        if over or config.shard_model:
            raise ValueError(
                f"{FAMILY}: no mesh axis is wired (got {over or 'shard_model=True'}): no sharding rule "
                "for the per-layer parameter tuple, no exchange of routed tokens over 'ep'"
            )
        if config.spec_layers:
            raise ValueError(f"{FAMILY}: spec_layers needs a verify step over the latent cache, which is not wired")

    def check_training(self, who: str) -> None:
        raise NotImplementedError(
            f"{who} cannot train a {FAMILY} model: no backward is wired (ops/moe.py's serving path is forward "
            "only, the latent attention here is the serving forms), and at 16 B a parameter no cut inside the "
            "floors fits a chip. Serve it: sample.py --engine=continuous, ServeEngine."
        )

    def check_serving(self, who: str) -> None:
        """sample.py's continuous engine and ServeEngine serve this family."""

    # -- shape helpers --
    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What a token keeps in a layer's cache: the normed latent and the rotated shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def mlp_kind(self, i: int) -> str:
        return "dense" if i < self.first_k_dense else "moe"

    @property
    def moe_layers(self) -> tp.Tuple[int, ...]:
        return tuple(i for i in range(self.n_layer) if self.mlp_kind(i) == "moe")


@pytree_dataclass
class MLAParams:
    w_qa: Array  # (q_lora_rank, D)
    q_norm: Array  # (q_lora_rank,)
    w_qb: Array  # (H * (nope + rope), q_lora_rank)
    w_kva: Array  # (kv_lora_rank + rope, D)
    kv_norm: Array  # (kv_lora_rank,)
    w_kvb: Array  # (H * (nope + v), kv_lora_rank): a head's rows are [k_n (nope); v]
    wo: Array  # (D, H * v)


@pytree_dataclass
class SwiGLUParams:
    w_gate: Array  # (F, D)
    w_up: Array  # (F, D)
    w_down: Array  # (D, F)


@pytree_dataclass
class MoEParams:
    router: Array  # (n_experts, D); no correction bias
    w_gate: Array  # (n_experts_held, F, D)
    w_up: Array  # (n_experts_held, F, D)
    w_down: Array  # (n_experts_held, D, F)
    shared: SwiGLUParams  # width n_shared_experts * F, whole on every chip


@pytree_dataclass
class LayerParams:
    norm_in: Array  # (D,) before attention
    attn: MLAParams
    norm_post_attn: tp.Optional[Array]  # (D,) on attention's output; None without `sandwich_norm`
    norm_pre_mlp: Array  # (D,)
    mlp: tp.Union[SwiGLUParams, MoEParams]
    norm_post_mlp: tp.Optional[Array]


@pytree_dataclass
class PanguUltraParams:
    wte: Array  # (V, D)
    layers: tp.Tuple[LayerParams, ...]
    final_norm: Array  # (D,)
    lm_head: Array  # (V, D), untied


_F32_LEAVES = ("norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp", "q_norm", "kv_norm", "final_norm", "router")


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "name", path[-1]))


def _linear(key: KeyArray, out_features: int, in_features: int) -> Array:
    return jax.random.truncated_normal(key, -2.0, 2.0, (out_features, in_features)) / math.sqrt(in_features)


def _norm(c: PanguUltraConfig, x: Array, w: tp.Optional[Array]) -> Array:
    if w is None:  # a post-norm of a model without `sandwich_norm`
        return x
    return rms_norm(x.astype(jnp.float32), w.astype(jnp.float32), c.rms_norm_eps).astype(x.dtype)


def _embed(params: "PanguUltraParams", tokens: Array) -> Array:
    with jax.named_scope("embed"):
        return jnp.take(params.wte, tokens, axis=0)


def _gather_latents(pool: Array, li: int, ids: Array, width: int) -> Array:
    """Pages `ids` (..., n) of layer `li` contiguous: (..., n * ps, width).
    ONE gather whose indices carry the layer (PagedKVCache "Layout contract",
    rule 3); the pool's lanes past `width` are padding."""
    g = pool[li, 0, ids][..., :width]  # (..., n, ps, width)
    return g.reshape(*g.shape[:-3], g.shape[-3] * g.shape[-2], width)


class PanguUltra:
    """Namespace of pure functions over (PanguUltraConfig, PanguUltraParams)."""

    weight_decay_mask = None
    route_stats = None
    # no speculative verify: the published drafter is the next-token-prediction layer, which reads the
    # target's last hidden state and is left out; the engine's draft model is a GPT (sampling/spec.py)
    verify_step_paged = None
    prefill_batched = False  # one row a call: the sweep over cached latents is bounded by ONE slot's length

    @staticmethod
    def init(config: PanguUltraConfig, key: KeyArray) -> PanguUltraParams:
        c = config
        D, H = c.n_embd, c.n_head
        post = (lambda g=1.0: jnp.full((D,), g)) if c.sandwich_norm else (lambda g=1.0: None)

        def init_mla(k: KeyArray) -> MLAParams:
            ks = jax.random.split(k, 5)
            return MLAParams(
                w_qa=_linear(ks[0], c.q_lora_rank, D), q_norm=jnp.ones((c.q_lora_rank,)),
                w_qb=_linear(ks[1], H * c.qk_head_dim, c.q_lora_rank),
                w_kva=_linear(ks[2], c.latent_dim, D), kv_norm=jnp.ones((c.kv_lora_rank,)),
                w_kvb=_linear(ks[3], H * (c.qk_nope_head_dim + c.v_head_dim), c.kv_lora_rank),
                wo=_linear(ks[4], D, H * c.v_head_dim),
            )

        def init_swiglu(k: KeyArray, F: int) -> SwiGLUParams:
            kg, ku, kd = jax.random.split(k, 3)
            return SwiGLUParams(w_gate=_linear(kg, F, D), w_up=_linear(ku, F, D), w_down=_linear(kd, D, F))

        def init_moe(k: KeyArray) -> MoEParams:
            kr, ke, ks = jax.random.split(k, 3)
            e = jax.vmap(lambda kk: init_swiglu(kk, c.expert_width))(jax.random.split(ke, c.n_experts_held))
            return MoEParams(router=_linear(kr, c.n_experts, D), w_gate=e.w_gate, w_up=e.w_up, w_down=e.w_down,
                             shared=init_swiglu(ks, c.n_shared_experts * c.expert_width))

        k_embed, k_head, k_layers = jax.random.split(key, 3)
        layers = []
        for i, k in enumerate(jax.random.split(k_layers, c.n_layer)):
            k_att, k_mlp = jax.random.split(k)
            layers.append(LayerParams(
                norm_in=jnp.ones((D,)), attn=init_mla(k_att), norm_post_attn=post(POST_ATTN_NORM_INIT),
                norm_pre_mlp=jnp.ones((D,)),
                mlp=init_swiglu(k_mlp, c.dense_width) if c.mlp_kind(i) == "dense" else init_moe(k_mlp),
                norm_post_mlp=post(),
            ))
        return PanguUltraParams(
            wte=jax.random.normal(k_embed, (c.vocab_size, D)) / math.sqrt(D),
            layers=tuple(layers), final_norm=jnp.ones((D,)),
            lm_head=jax.random.normal(k_head, (c.vocab_size, D)) / math.sqrt(D),
        )

    @staticmethod
    def cast_params(params: PanguUltraParams, dtype) -> PanguUltraParams:
        """The compute copy: matrices in `dtype`; norm weights and the router
        (a near tie decided in bf16 picks another expert) as they are."""
        return jax.tree_util.tree_map_with_path(
            lambda path, p: p if _leaf_name(path) in _F32_LEAVES or not jnp.issubdtype(p.dtype, jnp.floating)
            else p.astype(dtype),
            params,
        )

    @staticmethod
    def count_params(params: PanguUltraParams) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    @staticmethod
    def param_specs(config, tree, mesh):
        del config, mesh  # every leaf replicated: no mesh axis is wired (check_experiment)
        return jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree)

    @staticmethod
    def flops_per_token(config: PanguUltraConfig, seq_len: tp.Optional[int] = None, stats=None) -> float:
        """FORWARD FLOPs a token of what is computed here (this family is
        served, not trained): 2 x the parameters a token multiplies (a routed
        expert at the balanced share top_k * held / n_experts), plus scores and
        values of the published (expanded) form over a causal context."""
        del stats
        c = config
        T = seq_len or c.block_size
        H, r = c.n_head, c.kv_lora_rank
        mla = (c.n_embd * (c.q_lora_rank + c.latent_dim) + c.q_lora_rank * H * c.qk_head_dim
               + r * H * (c.qk_nope_head_dim + c.v_head_dim) + H * c.v_head_dim * c.n_embd)
        total = c.vocab_size * c.n_embd
        for i in range(c.n_layer):
            total += mla + H * (c.qk_head_dim + c.v_head_dim) * T / 2
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
    def _rope(c: PanguUltraConfig) -> tp.Tuple[Array, Array]:
        return rope_table(c.qk_rope_head_dim, c.block_size, c.rope_theta)

    @staticmethod
    def _q(c: PanguUltraConfig, p: MLAParams, h: Array, rope, positions: Array) -> tp.Tuple[Array, Array]:
        """h (B, T, D) -> (q_n (B, T, H, nope), q_r (B, T, H, rope) rotated at `positions`)."""
        B, T, _ = h.shape
        with jax.named_scope("mla_q"):
            c_q = _norm(c, jnp.einsum("btd,ed->bte", h, p.w_qa), p.q_norm)
            q = jnp.einsum("bte,fe->btf", c_q, p.w_qb).reshape(B, T, c.n_head, c.qk_head_dim)
            return q[..., :c.qk_nope_head_dim], apply_rope_leading(q[..., c.qk_nope_head_dim:], *rope, positions)

    @staticmethod
    def _latent(c: PanguUltraConfig, p: MLAParams, h: Array, rope, positions: Array) -> Array:
        """h (B, T, D) -> what the cache keeps of each token, (B, T, latent_dim):
        [n_kv(c_kv); k_r rotated at `positions`]."""
        with jax.named_scope("mla_kv"):
            ckv = jnp.einsum("btd,ed->bte", h, p.w_kva)
            lat = _norm(c, ckv[..., :c.kv_lora_rank], p.kv_norm)
            k_r = apply_rope_leading(ckv[..., None, c.kv_lora_rank:], *rope, positions)[..., 0, :]
            return jnp.concatenate([lat, k_r], axis=-1)

    @staticmethod
    def _up(c: PanguUltraConfig, p: MLAParams) -> tp.Tuple[Array, Array]:
        """W_kvb as (W_uk (H, nope, r), W_uv (H, v, r))."""
        w = p.w_kvb.reshape(c.n_head, c.qk_nope_head_dim + c.v_head_dim, c.kv_lora_rank)
        return w[:, :c.qk_nope_head_dim], w[:, c.qk_nope_head_dim:]

    @staticmethod
    def _expand(c: PanguUltraConfig, p: MLAParams, lat: Array) -> tp.Tuple[Array, Array]:
        """Cached rows (..., S, latent_dim) -> K (..., S, H, nope + rope), V (...,
        S, H, v) of every head: the published form, k_r shared by the heads."""
        r = c.kv_lora_rank
        kv = jnp.einsum("...sr,er->...se", lat[..., :r], p.w_kvb)
        kv = kv.reshape(*lat.shape[:-1], c.n_head, c.qk_nope_head_dim + c.v_head_dim)
        k_r = jnp.broadcast_to(lat[..., None, r:], (*lat.shape[:-1], c.n_head, c.qk_rope_head_dim))
        return jnp.concatenate([kv[..., :c.qk_nope_head_dim], k_r], axis=-1), kv[..., c.qk_nope_head_dim:]

    @staticmethod
    def _absorb_q(c: PanguUltraConfig, p: MLAParams, q_n: Array, q_r: Array) -> Array:
        """(..., H, nope), (..., H, rope) -> the query that scores LATENT rows,
        (..., H, latent_dim): [q_n W_uk; q_r]."""
        w_uk, _ = PanguUltra._up(c, p)
        return jnp.concatenate([jnp.einsum("...hn,hnr->...hr", q_n, w_uk).astype(q_r.dtype), q_r], axis=-1)

    @staticmethod
    def _out(c: PanguUltraConfig, p: MLAParams, o: Array, absorbed: bool) -> Array:
        """Attention's values a head -> (..., D): `absorbed`: o is (..., H, r),
        sums of latents, and W_kvb's value half is applied here; else (..., H, v)."""
        with jax.named_scope("mla_out"):
            if absorbed:
                _, w_uv = PanguUltra._up(c, p)
                o = jnp.einsum("...hr,hvr->...hv", o, w_uv)
            return jnp.einsum("...e,de->...d", o.reshape(*o.shape[:-2], c.n_head * c.v_head_dim), p.wo)

    @staticmethod
    def _moe(c: PanguUltraConfig, p: MoEParams, x: Array) -> tp.Tuple[Array, Array, tp.Dict[str, Array]]:
        """x (N, D) -> (the shared expert + the held experts' part of the routed
        layer (N, D), idx (N, k), stats)."""
        y, idx, stats = moe_serving(x, p.router, jnp.zeros((c.n_experts,), jnp.float32), p.w_gate, p.w_up, p.w_down,
                                    top_k=c.moe_top_k, scale=c.routed_scaling_factor, renormalize=c.moe_renormalize,
                                    offset=c.expert_offset)
        with jax.named_scope("moe_shared"):
            y = y + swiglu(x, p.shared.w_gate, p.shared.w_up, p.shared.w_down)
        return y, idx, stats

    @staticmethod
    def _ffn(c: PanguUltraConfig, i: int, p: LayerParams, x: Array):
        """x (B, T, D) + n_post_mlp(F(n_pre_mlp(x))); (x, idx | None, stats | None)."""
        with jax.named_scope("mlp"):
            h = _norm(c, x, p.norm_pre_mlp)
            if c.mlp_kind(i) == "dense":
                y = swiglu(h, p.mlp.w_gate, p.mlp.w_up, p.mlp.w_down)
                return x + _norm(c, y, p.norm_post_mlp), None, None
            B, T, D = h.shape
            y, idx, stats = PanguUltra._moe(c, p.mlp, h.reshape(B * T, D))
            return x + _norm(c, y.reshape(B, T, D), p.norm_post_mlp), idx, stats

    @staticmethod
    def _head(c: PanguUltraConfig, params: PanguUltraParams, x: Array) -> Array:
        with jax.named_scope("final_norm"):
            x = _norm(c, x, params.final_norm)
        return jnp.einsum("btd,vd->btv", x, params.lm_head)

    # ------------------------------------------------------------------
    # the plain full forward (tests; no cache): the published, expanded form
    # ------------------------------------------------------------------

    @staticmethod
    def hidden(config: PanguUltraConfig, params: PanguUltraParams, tokens: Array, *, key=None,
               inference: bool = False, attn_fn=None) -> Array:
        """Backbone forward over whole sequences (B, T) with an explicit mask
        -> final-normed hidden states (B, T, D)."""
        del key, inference
        if attn_fn is not None:
            raise ValueError(f"{FAMILY}: a mesh-bound attn_fn is not wired")
        c = config
        B, T = tokens.shape
        rope, pos = PanguUltra._rope(c), jnp.arange(T)
        keep = pos[None, :] <= pos[:, None]
        x = _embed(params, tokens)
        for i, p in enumerate(params.layers):
            with jax.named_scope("attn"), jax.named_scope("attn_latent"):
                h = _norm(c, x, p.norm_in)
                q = jnp.concatenate(PanguUltra._q(c, p.attn, h, rope, pos), axis=-1)
                k, v = PanguUltra._expand(c, p.attn, PanguUltra._latent(c, p.attn, h, rope, pos))
                s = jnp.einsum("bthc,bshc->bhts", q, k).astype(jnp.float32) / math.sqrt(c.qk_head_dim)
                prob = jax.nn.softmax(jnp.where(keep, s, MASK), axis=-1).astype(v.dtype)
                o = jnp.einsum("bhts,bshc->bthc", prob, v)
                x = x + _norm(c, PanguUltra._out(c, p.attn, o, absorbed=False), p.norm_post_attn)
            x, _, _ = PanguUltra._ffn(c, i, p, x)
        with jax.named_scope("final_norm"):
            return _norm(c, x, params.final_norm)

    @staticmethod
    def apply(config: PanguUltraConfig, params: PanguUltraParams, tokens: Array) -> Array:
        """Logits (B, T, V) of whole sequences."""
        return jnp.einsum("btd,vd->btv", PanguUltra.hidden(config, params, tokens), params.lm_head)

    # ------------------------------------------------------------------
    # serving (sampling/serve.py reaches these through models/__init__.py)
    # ------------------------------------------------------------------

    @staticmethod
    def cache_kinds(config: PanguUltraConfig) -> tp.Tuple[CacheKind, ...]:
        """One kind: every layer keeps the whole context's latents."""
        return (CacheKind(LATENT, 0, 0),)

    @staticmethod
    def init_cache(config: PanguUltraConfig, num_pages: tp.Sequence[int], page_size: int = 8,
                   dtype=jnp.bfloat16, kernel_layout: bool = False) -> ServeCache:
        """A zeroed pool of `num_pages[0]` pages: ONE array of one head, a
        token's row stored once (module docstring). Counters: the expert
        layers' `(moe_counts, moe_totals)`."""
        c = config
        return ServeCache.zeros(FAMILY, (((c.n_layer, 1, c.latent_dim),),), num_pages, page_size, dtype, kernel_layout,
                                moe_counters_init(len(c.moe_layers), c.n_experts_held))

    kernel_sweep_whole = True  # every layer's absorbed decode is this one kernel call

    @staticmethod
    def kernel_sweep(config: PanguUltraConfig, cache: ServeCache) -> tp.Tuple[tp.Tuple[int, ...], int, int, int]:
        """(pool shape, q rows a pool head, window, sinks) of the decode
        kernel's sweep, for the engine's block counters: every head's query
        row against the pool's one head."""
        return cache.pools[0][0].shape, config.n_head, 0, 0

    @staticmethod
    def serve_counters(config: PanguUltraConfig, cache: ServeCache) -> tp.Dict[str, float]:
        """The expert layers' counters (ops/moe.py `moe_serve_counters`), and
        what the pool keeps of a token over all layers, in bytes: `n_layer` rows
        of the pool's lanes (the latent stored ONCE; K and V of every head
        would be 64 times that)."""
        pool = cache.pools[0][0]
        return {**moe_serve_counters(*cache.counters),
                "kv.latent_bytes_per_token": pool.nbytes / (pool.shape[2] * pool.shape[3])}

    @staticmethod
    def _absorbed_attention(c: PanguUltraConfig, q: Array, pool: Array, li: int, table: Array, counts: Array) -> Array:
        """XLA gather form of the absorbed decode: q (B, H, latent_dim) against
        the slot's cached latents; row b sees `counts[b]` keys. -> (B, H, r): the
        softmax-weighted sums of LATENTS (W_uv is applied by the caller)."""
        lat = _gather_latents(pool, li, table, c.latent_dim)  # (B, S, latent_dim)
        s = jnp.einsum("bhc,bsc->bhs", q.astype(lat.dtype), lat).astype(jnp.float32) / math.sqrt(c.qk_head_dim)
        keep = jnp.arange(lat.shape[1], dtype=jnp.int32)[None, None, :] < counts[:, None, None]
        prob = jax.nn.softmax(jnp.where(keep, s, MASK), axis=-1).astype(lat.dtype)
        return jnp.einsum("bhs,bsr->bhr", prob, lat[..., :c.kv_lora_rank])

    @staticmethod
    def decode_step_paged(config: PanguUltraConfig, params: PanguUltraParams, token: Array, cache: ServeCache,
                          page_table: Array, lengths: Array, active: Array,
                          attn_impl: str = "auto", mesh=None, split_k: int = 1) -> tp.Tuple[Array, ServeCache]:
        """One decode step for B requests at B positions (GPT.decode_step_paged's
        contract). Slot b writes its token's latent row at position lengths[b]
        in every layer and attends, ABSORBED, to lengths[b] + 1 cached rows.
        Inactive slots write nothing and read one masked-in garbage key.
        Returns (logits (B, V), cache)."""
        from midgpt_tpu.kernels.decode_attention import resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        ps, pool = cache.page_size, cache.pools[0][0]
        pos = lengths
        counts = jnp.maximum(active.astype(jnp.int32) * (pos + 1), 1)  # (B,)
        rope = PanguUltra._rope(c)
        write_pages = jnp.where(active, jnp.take_along_axis(page_table, (pos // ps)[:, None], axis=1)[:, 0], pool.shape[2])
        moe_counts, totals = cache.counters
        x = _embed(params, token[:, None])  # (B, 1, D)
        n_moe = 0
        for i, p in enumerate(params.layers):
            with jax.named_scope("attn"), jax.named_scope("attn_latent"):
                h = _norm(c, x, p.norm_in)
                q_n, q_r = PanguUltra._q(c, p.attn, h, rope, pos[:, None])
                with jax.named_scope("mla_q"):
                    q = PanguUltra._absorb_q(c, p.attn, q_n[:, 0], q_r[:, 0])  # (B, H, latent_dim)
                row = PanguUltra._latent(c, p.attn, h, rope, pos[:, None])[:, 0]  # (B, latent_dim)
                pool, _, _, _ = _paged_write((pool, None, None, None), jnp.asarray(i), write_pages, pos % ps,
                                             row[:, None, :], None, attn_impl, None)
                if attn_impl == "kernel":
                    from midgpt_tpu.kernels.attention_template import paged_attention_template

                    o = paged_attention_template(
                        q[:, :, None, :], pool, None, page_table, counts[:, None], split_k=split_k,
                        layer=jnp.asarray(i), v_lanes=c.kv_lora_rank, scale=1.0 / math.sqrt(c.qk_head_dim),
                    )[:, :, 0]  # (B, H, r)
                else:
                    o = PanguUltra._absorbed_attention(c, q, pool, i, page_table, counts)
                o = PanguUltra._out(c, p.attn, o.astype(x.dtype), absorbed=True)[:, None]
                x = x + _norm(c, o, p.norm_post_attn)
            x, idx, stats = PanguUltra._ffn(c, i, p, x)
            if idx is not None:
                moe_counts, totals = moe_count_decode(moe_counts, totals, n_moe, idx, active, stats,
                                                      offset=c.expert_offset)
                n_moe += 1
        totals = totals.at[0].add(1)
        logits = PanguUltra._head(c, params, x)[:, 0]
        return logits, ServeCache(pools=((pool,),), counters=(moe_counts, totals))

    @staticmethod
    def prefill_paged_chunk(config: PanguUltraConfig, params: PanguUltraParams, tokens: Array, start: Array,
                            n_valid: Array, cache: ServeCache, page_table: Array,
                            attn_impl: str = "auto", mesh=None) -> tp.Tuple[Array, ServeCache]:
        """One request's prompt chunk [start, start + n_valid) into its pages
        (the ONE-ROW call of models/__init__.py: tokens (1, T), scalar start /
        n_valid, `page_table` the slot's (1, pages) row). Returns (logits of
        the LAST VALID row (1, 1, V), cache)."""
        from midgpt_tpu.kernels.decode_attention import resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        _, T = tokens.shape
        ps, pool = cache.page_size, cache.pools[0][0]
        t_idx = jnp.arange(T, dtype=jnp.int32)
        positions = start + t_idx
        valid = t_idx < n_valid
        counts = jnp.minimum(positions, start + n_valid - 1) + 1  # pad rows see what the last valid row sees
        rope = PanguUltra._rope(c)
        write_pages = jnp.where(valid, jnp.take(page_table[0], positions // ps, axis=0), pool.shape[2])
        moe_counts, totals = cache.counters
        x = _embed(params, tokens)  # (1, T, D)
        for i, p in enumerate(params.layers):
            with jax.named_scope("attn"), jax.named_scope("attn_latent"):
                h = _norm(c, x, p.norm_in)
                q_n, q_r = PanguUltra._q(c, p.attn, h, rope, positions)
                rows = PanguUltra._latent(c, p.attn, h, rope, positions)[0]  # (T, latent_dim)
                pool, _, _, _ = _paged_write((pool, None, None, None), jnp.asarray(i), write_pages, positions % ps,
                                             rows[:, None, :], None, attn_impl, None)
                o = PanguUltra._prefill_sweep(c, p.attn, jnp.concatenate([q_n, q_r], axis=-1)[0], pool, i,
                                              page_table[0], counts)
                x = x + _norm(c, PanguUltra._out(c, p.attn, o.astype(x.dtype), absorbed=False)[None], p.norm_post_attn)
            x, idx, stats = PanguUltra._ffn(c, i, p, x)
            if idx is not None:
                totals = moe_count_dropped(totals, stats["dropped"])
        last = jax.lax.dynamic_slice_in_dim(x, jnp.maximum(n_valid - 1, 0), 1, axis=1)  # (1, 1, D)
        logits = PanguUltra._head(c, params, last)
        return logits, ServeCache(pools=((pool,),), counters=(moe_counts, totals))

    @staticmethod
    def _prefill_sweep(c: PanguUltraConfig, p: MLAParams, q: Array, pool: Array, li: int, table_row: Array,
                       counts: Array) -> Array:
        """A chunk's rows q (T, H, nope + rope) against the slot's cached
        latents (the chunk's own included: they were written first), in blocks
        of `PREFILL_KEY_BLOCK` keys, each block EXPANDED to K and V of every
        head (`_expand`) and swept with an online softmax; the loop runs over
        the blocks that hold a visible key, not over the table. Row t sees
        `counts[t]` keys. -> (T, H, v)."""
        T, H, _ = q.shape
        ps, MP = pool.shape[3], table_row.shape[0]
        kp = max(1, min(MP, PREFILL_KEY_BLOCK // ps))  # pages a block
        scale = 1.0 / math.sqrt(c.qk_head_dim)

        def body(b, carry):
            m, l, acc = carry
            page = b * kp + jnp.arange(kp, dtype=jnp.int32)
            ids = jnp.take(table_row, jnp.minimum(page, MP - 1), axis=0)  # past the table: masked (col >= any count)
            lat = _gather_latents(pool, li, ids, c.latent_dim)  # (kp * ps, latent_dim)
            k, v = PanguUltra._expand(c, p, lat.astype(q.dtype))  # (S, H, .)
            s = jnp.einsum("thc,shc->hts", q, k).astype(jnp.float32) * scale
            col = b * (kp * ps) + jnp.arange(kp * ps, dtype=jnp.int32)
            s = jnp.where(col[None, None, :] < counts[None, :, None], s, MASK)
            m, alpha, prob, l = online_block(m, l, s)
            pv = jnp.einsum("hts,shc->htc", prob.astype(v.dtype), v).astype(jnp.float32)
            return m, l, acc * alpha[..., None] + pv

        init = (jnp.full((H, T), M_INIT, jnp.float32), jnp.zeros((H, T), jnp.float32),
                jnp.zeros((H, T, c.v_head_dim), jnp.float32))
        n_live = (counts[-1] + kp * ps - 1) // (kp * ps)
        m, l, acc = jax.lax.fori_loop(0, n_live, body, init)
        out, _ = finalize(m, l, acc)
        return jnp.transpose(out, (1, 0, 2))  # (T, H, v)
