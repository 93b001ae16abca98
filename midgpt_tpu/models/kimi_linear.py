"""Kimi-Linear: a hybrid of KDA linear-attention layers and NoPE latent
attention (MLA) layers over SwiGLU MLPs, the first `first_k_dense` dense and
the rest a sigmoid-routed mixture of experts with a shared expert.

Source: https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json
(27 layers, hidden 2,304, 20 KDA : 7 MLA in a 3:1 pattern, 256 experts, top-8).
Like `models/gpt.py` this is a plain pytree + a namespace of pure functions,
and the training runtime reaches it through `model_config.model()` and the
members every family has (models/__init__.py). Unlike the GPT its layers
differ by KIND, so the parameters are a tuple of per-layer pytrees and the
forward is a Python loop over them (each layer `jax.checkpoint`ed), not one
`lax.scan` over a stacked axis.

A layer, with h its input (B, T, D) and RMSNorm carrying a weight (eps 1e-5):

    x = x + Mixer(norm1(x));  x = x + MLP(norm2(x))

KDA mixer (layer numbers in `kda_layers`, 1-based as published): q, k, v =
SiLU(conv4(W h)), conv4 a causal depthwise convolution; q, k L2-normalised
per head, q scaled by d_k^-1/2; per-channel log decay g = -exp(A_log[head]) *
softplus(W_fb(W_fa h) + dt_bias); beta = sigmoid(W_b h); the gated delta rule
of `ops/kda.py`; output W_o(RMSNorm_w(o) * sigmoid(W_gb(W_ga h))).

MLA mixer (layers in `full_attn_layers`): q = W_q h as (H, 192); [c, k_pe] =
W_kva h; [k_nope, v] = W_kvb RMSNorm_w(c); k = [k_nope, k_pe] with k_pe shared
by all heads and NOT rotated (`mla_use_nope`); causal softmax(q k^T /
sqrt(192)) v; W_o. On a TPU the flash kernels take one head width, so q and k
are zero-padded from 192 and v from 128 to 256 channels around the call and q
is pre-scaled by sqrt(256/192): the same scores, a layout choice.

MoE (`ops/moe.py`): s = sigmoid(W_r h) over all `n_experts`; top-k of s +
bias; weights the selected s renormalised and scaled; this chip adds what the
experts it HOLDS (`[expert_offset, expert_offset + n_experts_held)`) give,
plus the whole shared expert. With n_experts_held == n_experts that is the
whole layer.

What this model does not do yet stops with a plain error: serving
(`sample.py`, `ServeEngine`), any mesh axis other than `data` above 1, and
attention implementations other than `flash` and `naive`.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.ops.kda import causal_depthwise_conv, kda_chunked
from midgpt_tpu.ops.moe import moe_capacity, moe_experts, route, swiglu
from midgpt_tpu.ops.norms import rms_norm
from midgpt_tpu.utils.pytree import pytree_dataclass

Array = jax.Array
KeyArray = jax.Array

FAMILY = "kimi_linear"
# Rows of the dispatch buffer the held experts share, as a multiple of the mean
# number of pairs routed here; more than that takes ops/moe.py's exact path. At
# initialisation the pairs routed here read 0.45-1.7 x their mean over 16 seeds
# x 4 layers on the chip (PERF.md §6 PR 26).
MOE_CAPACITY_FACTOR = 2.0
_MLA_ATTN_IMPLS = ("flash", "naive")


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """Model shape, by the published keys' meaning. The first five fields are
    named as `GPTConfig` names them: the benchmark's readers and the data path
    take `dataclasses.asdict(model_config)`."""

    block_size: int  # training sequence length
    vocab_size: int  # rows of wte / lm_head held here
    n_layer: int  # num_hidden_layers
    n_head: int  # num_attention_heads (MLA) = linear_attn_config.num_heads (KDA)
    n_embd: int  # hidden_size
    # 1-based layer numbers, as published (linear_attn_config); numbers past
    # n_layer are ignored, so a depth cut keeps the lists whole.
    kda_layers: tp.Tuple[int, ...] = ()
    full_attn_layers: tp.Tuple[int, ...] = ()
    kda_head_dim: int = 128  # linear_attn_config.head_dim: d_k = d_v
    kda_conv_size: int = 4  # short_conv_kernel_size
    kda_gate_rank: int = 128  # rank of the decay and output-gate pairs (not in config.json: = head_dim)
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64  # extra q/k channels; NOT rotated (mla_use_nope)
    v_head_dim: int = 128
    dense_width: int = 9216  # intermediate_size
    first_k_dense: int = 1  # first_k_dense_replace
    n_experts: int = 256  # the router's width: num_experts
    n_experts_held: int = 256  # experts whose weights live here
    expert_offset: int = 0  # first expert held here
    moe_top_k: int = 8  # num_experts_per_token
    expert_width: int = 1024  # moe_intermediate_size (routed and shared)
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    rms_norm_eps: float = 1e-5
    attn_impl: str = "flash"  # MLA softmax attention: 'flash' (TPU) | 'naive'
    attn_block_size: int = 512
    family: str = FAMILY  # discriminates model_config in config.json

    def __post_init__(self):
        for name in ("kda_layers", "full_attn_layers"):
            object.__setattr__(self, name, tuple(int(i) for i in getattr(self, name)))
        if self.family != FAMILY:
            raise ValueError(f"family={self.family!r} is not {FAMILY!r}")
        kinds = [self.mixer_kind(i) for i in range(self.n_layer)]  # raises on a layer in neither list
        if self.attn_impl not in _MLA_ATTN_IMPLS and "mla" in kinds:
            raise ValueError(
                f"attn_impl={self.attn_impl!r}: the MLA layers take {_MLA_ATTN_IMPLS} only "
                "(q/k of 192 channels beside v of 128; ring, ulysses and blockwise "
                "attention assume one head width and are not wired to this model)"
            )
        if not (0 <= self.expert_offset and self.expert_offset + self.n_experts_held <= self.n_experts):
            raise ValueError(
                f"experts held [{self.expert_offset}, {self.expert_offset + self.n_experts_held}) "
                f"lie outside the router's {self.n_experts}"
            )
        if not 1 <= self.moe_top_k <= self.n_experts:
            raise ValueError(f"moe_top_k={self.moe_top_k} must be in [1, n_experts={self.n_experts}]")

    # -- what the runtime reads of any model config --
    def model(self):
        return KimiLinear

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def mixer_kind(self, i: int) -> str:
        """'kda' or 'mla' for 0-based layer i."""
        if i + 1 in self.kda_layers:
            return "kda"
        if i + 1 in self.full_attn_layers:
            return "mla"
        raise ValueError(f"layer {i + 1} is in neither kda_layers nor full_attn_layers")

    def mlp_kind(self, i: int) -> str:
        return "dense" if i < self.first_k_dense else "moe"

    def check_serving(self, who: str) -> None:
        raise NotImplementedError(
            f"{who} cannot serve a {FAMILY} checkpoint yet. The serving stack holds paged pools of several kinds side "
            "by side (models/mimo_v2.py), a latent (MLA) pool with its absorbed decode (models/pangu_ultra.py) and, "
            "since models/olmo_hybrid.py, a STATE kind of cache (a row a slot, reset by a prompt's first chunk, carried from "
            "prefill chunk to prefill chunk: sampling/pages.py) with a one-token and a state-carrying chunked "
            "delta-rule step (ops/kda.py kda_step / kda_chunked(initial_state=)), both of which take this model's "
            "per-channel gate as they are. What is STILL missing for this family: a NoPE variant of the latent row "
            "(its MLA layers rotate no key group, the latent pool's row has one), this family's serving members "
            "(cache_kinds, init_cache, prefill_paged_chunk, decode_step_paged over a latent kind AND a state kind), "
            "and the routed experts' serving path at its shapes. Train it with launch.py; ROADMAP.md M2."
        )

    def check_training(self, who: str) -> None:
        """launch.py trains this family."""

    def check_experiment(self, config) -> None:
        """`ExperimentConfig.__post_init__` for this family: what the training
        runtime cannot do with this model yet stops here, by name."""
        m = config.mesh
        over = {a: getattr(m, a) for a in ("fsdp", "sp", "tp", "pp", "ep") if getattr(m, a) not in (1, -1)}
        if over or config.shard_model:
            raise ValueError(
                f"{FAMILY}: only the data-parallel mesh is wired (got {over or 'shard_model=True'}): "
                "no sharding rule for the per-layer parameter tuple in parallel/tp.py or "
                "parallel/fsdp.py, no all-to-all for experts over 'ep', no sequence split of "
                "the KDA state over 'sp', no stage split in parallel/pipeline.py"
            )
        if config.moe_aux_coef != 0.0:
            # (a forced fsdp_mode='shard_map' is refused for every family but
            # the GPT by ExperimentConfig.authored_fsdp_refusal)
            raise ValueError(f"{FAMILY}: moe_aux_coef=0.0 only")
        if config.spec_layers:
            raise ValueError(f"{FAMILY}: spec_layers is a serving knob and serving is not wired")


@pytree_dataclass
class KDAParams:
    w_qkv: Array  # (3, H*d, D) q, k, v projections
    conv: Array  # (3, H*d, K) causal depthwise taps of q, k, v; the last tap is the current token
    w_fa: Array  # (r, D)   decay, low-rank pair
    w_fb: Array  # (H*d, r)
    A_log: Array  # (H,)
    dt_bias: Array  # (H*d,)
    w_b: Array  # (H, D) beta
    w_ga: Array  # (r, D)   output gate, low-rank pair
    w_gb: Array  # (H*d, r)
    o_norm: Array  # (d,) weight of the per-head output RMSNorm
    wo: Array  # (D, H*d)


@pytree_dataclass
class MLAParams:
    wq: Array  # (H*(nope+rope), D)
    w_kva: Array  # (kv_lora_rank + rope, D)
    kv_norm: Array  # (kv_lora_rank,)
    w_kvb: Array  # (H*(nope+v), kv_lora_rank)
    wo: Array  # (D, H*v)


@pytree_dataclass
class SwiGLUParams:
    w_gate: Array  # (F, D)
    w_up: Array  # (F, D)
    w_down: Array  # (D, F)


@pytree_dataclass
class MoEParams:
    router: Array  # (n_experts, D)
    router_bias: Array  # (n_experts,) e_score_correction_bias: selection only, gradient 0
    w_gate: Array  # (n_experts_held, F, D)
    w_up: Array  # (n_experts_held, F, D)
    w_down: Array  # (n_experts_held, D, F)
    shared: SwiGLUParams  # width n_shared_experts * F


@pytree_dataclass
class LayerParams:
    norm1: Array  # (D,)
    mixer: tp.Union[KDAParams, MLAParams]
    norm2: Array  # (D,)
    mlp: tp.Union[SwiGLUParams, MoEParams]


@pytree_dataclass
class KimiLinearParams:
    wte: Array  # (V, D)
    layers: tp.Tuple[LayerParams, ...]  # one pytree per layer; kinds differ
    final_norm: Array  # (D,)
    lm_head: Array  # (V, D), untied


# Leaves that stay float32 under `cast_params` (small, and a rounding of theirs
# is a different model: decay rates, the router's near ties) and take no
# weight decay (`weight_decay_mask`: norm weights and what is not a matrix).
_NORM_LEAVES = ("norm1", "norm2", "final_norm", "kv_norm", "o_norm")
_F32_LEAVES = _NORM_LEAVES + ("A_log", "dt_bias", "router", "router_bias")
_NO_DECAY_LEAVES = _NORM_LEAVES + ("A_log", "dt_bias", "router_bias", "conv")


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "name", path[-1]))


def _linear(key: KeyArray, out_features: int, in_features: int) -> Array:
    w = jax.random.truncated_normal(key, -2.0, 2.0, (out_features, in_features))
    return w / math.sqrt(in_features)


def _swiglu_init(key: KeyArray, D: int, F: int) -> SwiGLUParams:
    kg, ku, kd = jax.random.split(key, 3)
    return SwiGLUParams(w_gate=_linear(kg, F, D), w_up=_linear(ku, F, D), w_down=_linear(kd, D, F))


def _l2norm(x: Array, eps: float = 1e-6) -> Array:
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


class KimiLinear:
    """Namespace of pure functions over (KimiLinearConfig, KimiLinearParams)."""

    @staticmethod
    def init(config: KimiLinearConfig, key: KeyArray) -> KimiLinearParams:
        c = config
        D, H, d, r = c.n_embd, c.n_head, c.kda_head_dim, c.kda_gate_rank

        def init_kda(k: KeyArray) -> KDAParams:
            ks = jax.random.split(k, 10)
            # fla's convention for the family: A in U(1, 16); dt log-uniform in
            # [1e-3, 1e-1] and dt_bias its inverse softplus; taps U(+-1/sqrt(K))
            dt = jnp.exp(jax.random.uniform(ks[6], (H * d,), minval=math.log(1e-3), maxval=math.log(1e-1)))
            bound = 1.0 / math.sqrt(c.kda_conv_size)
            return KDAParams(
                w_qkv=jax.vmap(lambda kk: _linear(kk, H * d, D))(jax.random.split(ks[0], 3)),
                conv=jax.random.uniform(ks[1], (3, H * d, c.kda_conv_size), minval=-bound, maxval=bound),
                w_fa=_linear(ks[2], r, D), w_fb=_linear(ks[3], H * d, r),
                A_log=jnp.log(jax.random.uniform(ks[4], (H,), minval=1.0, maxval=16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                w_b=_linear(ks[5], H, D),
                w_ga=_linear(ks[7], r, D), w_gb=_linear(ks[8], H * d, r),
                o_norm=jnp.ones((d,)), wo=_linear(ks[9], D, H * d),
            )

        def init_mla(k: KeyArray) -> MLAParams:
            ks = jax.random.split(k, 4)
            return MLAParams(
                wq=_linear(ks[0], H * c.qk_head_dim, D),
                w_kva=_linear(ks[1], c.kv_lora_rank + c.qk_rope_head_dim, D),
                kv_norm=jnp.ones((c.kv_lora_rank,)),
                w_kvb=_linear(ks[2], H * (c.qk_nope_head_dim + c.v_head_dim), c.kv_lora_rank),
                wo=_linear(ks[3], D, H * c.v_head_dim),
            )

        def init_moe(k: KeyArray) -> MoEParams:
            kr, kb, ke, ksh = jax.random.split(k, 4)
            experts = jax.vmap(lambda kk: _swiglu_init(kk, D, c.expert_width))(
                jax.random.split(ke, c.n_experts_held)
            )
            return MoEParams(
                router=_linear(kr, c.n_experts, D),
                # the published bias is moved by a balancing rule outside the
                # gradient, which is not run here: a small seeded value, so
                # that selection and weights are seen to use different scores
                router_bias=0.01 * jax.random.normal(kb, (c.n_experts,)),
                w_gate=experts.w_gate, w_up=experts.w_up, w_down=experts.w_down,
                shared=_swiglu_init(ksh, D, c.n_shared_experts * c.expert_width),
            )

        k_embed, k_head, k_layers = jax.random.split(key, 3)
        layers = []
        for i, k in enumerate(jax.random.split(k_layers, c.n_layer)):
            k_mix, k_mlp = jax.random.split(k)
            layers.append(LayerParams(
                norm1=jnp.ones((D,)),
                mixer=init_kda(k_mix) if c.mixer_kind(i) == "kda" else init_mla(k_mix),
                norm2=jnp.ones((D,)),
                mlp=_swiglu_init(k_mlp, D, c.dense_width) if c.mlp_kind(i) == "dense" else init_moe(k_mlp),
            ))
        return KimiLinearParams(
            wte=jax.random.normal(k_embed, (c.vocab_size, D)) / math.sqrt(D),
            layers=tuple(layers),
            final_norm=jnp.ones((D,)),
            lm_head=jax.random.normal(k_head, (c.vocab_size, D)) / math.sqrt(D),
        )

    @staticmethod
    def cast_params(params: KimiLinearParams, dtype) -> KimiLinearParams:
        """The compute copy: matrices in `dtype`, `_F32_LEAVES` as they are."""
        return jax.tree_util.tree_map_with_path(
            lambda path, p: p if _leaf_name(path) in _F32_LEAVES or not jnp.issubdtype(p.dtype, jnp.floating)
            else p.astype(dtype),
            params,
        )

    @staticmethod
    def weight_decay_mask(params) -> tp.Any:
        """Tree of bools like `params`: False where AdamW's decay is NOT
        applied (norm weights, A_log, dt_bias, the router's correction bias,
        the convolution taps). Every matrix, the embedding and the head decay,
        as every GPT leaf does (training/optim.py)."""
        return jax.tree_util.tree_map_with_path(lambda path, _: _leaf_name(path) not in _NO_DECAY_LEAVES, params)

    @staticmethod
    def count_params(params: KimiLinearParams) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    @staticmethod
    def _kda(c: KimiLinearConfig, p: KDAParams, h: Array) -> Array:
        B, T, _ = h.shape
        H, d = c.n_head, c.kda_head_dim
        f32 = jnp.float32
        with jax.named_scope("kda"):
            qkv = jnp.einsum("btd,xed->xbte", h, p.w_qkv)
            q, k, v = (
                jax.nn.silu(causal_depthwise_conv(qkv[i], p.conv[i].astype(h.dtype))).reshape(B, T, H, d)
                for i in range(3)
            )
            q = (_l2norm(q.astype(f32)) * d**-0.5).astype(h.dtype)
            k = _l2norm(k.astype(f32)).astype(h.dtype)
            # decay, write strength and gate leave their last matmul in float32
            # (the accumulator's own precision: no rounding of the output to h's dtype)
            f = jnp.einsum("btr,er->bte", jnp.einsum("btd,rd->btr", h, p.w_fa), p.w_fb, preferred_element_type=f32)
            g = -jnp.exp(p.A_log.astype(f32))[:, None] * jax.nn.softplus(f + p.dt_bias.astype(f32)).reshape(B, T, H, d)
            beta = jax.nn.sigmoid(jnp.einsum("btd,hd->bth", h, p.w_b, preferred_element_type=f32))
            with jax.named_scope("kda_scan"):
                o, _ = kda_chunked(q, k, v, g, beta)
            gate = jnp.einsum("btr,er->bte", jnp.einsum("btd,rd->btr", h, p.w_ga), p.w_gb, preferred_element_type=f32)
            o = rms_norm(o.astype(f32), p.o_norm.astype(f32), c.rms_norm_eps)
            o = (o * jax.nn.sigmoid(gate).reshape(B, T, H, d)).astype(h.dtype)
            return jnp.einsum("bte,de->btd", o.reshape(B, T, H * d), p.wo)

    @staticmethod
    def _mla(c: KimiLinearConfig, p: MLAParams, h: Array) -> Array:
        B, T, _ = h.shape
        H, dn, dr, dv = c.n_head, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        dq = dn + dr
        with jax.named_scope("mla"):
            q = jnp.einsum("btd,ed->bte", h, p.wq).reshape(B, T, H, dq)
            ckv = jnp.einsum("btd,ed->bte", h, p.w_kva)
            lat = rms_norm(ckv[..., : c.kv_lora_rank].astype(jnp.float32), p.kv_norm.astype(jnp.float32), c.rms_norm_eps)
            kv = jnp.einsum("btr,er->bte", lat.astype(h.dtype), p.w_kvb).reshape(B, T, H, dn + dv)
            k_pe = jnp.broadcast_to(ckv[..., None, c.kv_lora_rank :], (B, T, H, dr))  # shared by the heads, not rotated
            k = jnp.concatenate([kv[..., :dn], k_pe], axis=-1)
            v = kv[..., dn:]
            q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))  # (B, H, T, .)
            if c.attn_impl == "flash":
                from midgpt_tpu.ops.attention import flash_block_sizes

                fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")
                W = -(-dq // 128) * 128  # the kernels take ONE head width and scale by its rsqrt
                q = jnp.pad(q * math.sqrt(W / dq), ((0, 0),) * 3 + ((0, W - dq),)).astype(h.dtype)
                k = jnp.pad(k, ((0, 0),) * 3 + ((0, W - dq),))
                v = jnp.pad(v, ((0, 0),) * 3 + ((0, W - dv),))
                bq, bk = flash_block_sizes(T, c.attn_block_size)
                o = fa.flash_attention(q, k, v, bq, bk)[..., :dv]
            else:
                s = jnp.einsum("bhqc,bhkc->bhqk", q, k).astype(jnp.float32) / math.sqrt(dq)
                s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
                o = jnp.einsum("bhqk,bhkc->bhqc", jax.nn.softmax(s, axis=-1).astype(h.dtype), v)
            o = jnp.swapaxes(o, 1, 2).reshape(B, T, H * dv)
            return jnp.einsum("bte,de->btd", o, p.wo)

    @staticmethod
    def _moe(c: KimiLinearConfig, p: MoEParams, h: Array) -> tp.Tuple[Array, tp.Dict[str, Array]]:
        B, T, D = h.shape
        x = h.reshape(B * T, D)
        with jax.named_scope("moe_route"):
            idx, w = route(
                x, p.router, p.router_bias, top_k=c.moe_top_k,
                scale=c.routed_scaling_factor, renormalize=c.moe_renormalize,
            )
        n_tiles, tile = moe_capacity(B * T, c.moe_top_k, c.n_experts, c.n_experts_held, MOE_CAPACITY_FACTOR)
        y, stats = moe_experts(
            x, idx, w, p.w_gate, p.w_up, p.w_down, offset=c.expert_offset, n_tiles=n_tiles, tile=tile)
        with jax.named_scope("moe_experts"):
            y = y + swiglu(x, p.shared.w_gate, p.shared.w_up, p.shared.w_down)
        return y.reshape(B, T, D), stats

    @staticmethod
    def layer_apply(c: KimiLinearConfig, i: int, p: LayerParams, x: Array) -> tp.Tuple[Array, tp.Optional[dict]]:
        f32 = jnp.float32
        norm = lambda a, w: rms_norm(a.astype(f32), w.astype(f32), c.rms_norm_eps).astype(a.dtype)
        stats = None
        with jax.named_scope("attn"):
            mixer = KimiLinear._kda if c.mixer_kind(i) == "kda" else KimiLinear._mla
            x = x + mixer(c, p.mixer, norm(x, p.norm1))
        with jax.named_scope("mlp"):
            h = norm(x, p.norm2)
            if c.mlp_kind(i) == "dense":
                y = swiglu(h, p.mlp.w_gate, p.mlp.w_up, p.mlp.w_down)
            else:
                y, stats = KimiLinear._moe(c, p.mlp, h)
            x = x + y
        return x, stats

    @staticmethod
    def hidden(
        config: KimiLinearConfig,
        params: KimiLinearParams,
        tokens: Array,  # (B, T) int
        *,
        key: tp.Optional[KeyArray] = None,
        inference: bool = False,
        attn_fn: tp.Optional[tp.Callable] = None,
        return_stats: bool = False,
    ) -> tp.Union[Array, tp.Tuple[Array, tp.Dict[str, Array]]]:
        """Backbone forward -> final-normed hidden states (B, T, D); the head
        is applied by the caller (fused into the loss in training). `key` and
        `inference` are the runtime's dropout arguments: this model has none.
        `return_stats` adds {"counts" (moe layers, n_experts_held), "dropped"
        (), "overflowed" () how many of the routed layers took ops/moe.py's
        exact path}."""
        del key, inference
        if attn_fn is not None:
            raise ValueError(f"{FAMILY}: a mesh-bound attn_fn (ring / ulysses / sharded flash) is not wired to MLA")
        with jax.named_scope("embed"):
            x = jnp.take(params.wte, tokens, axis=0)
        all_stats = []
        for i, layer in enumerate(params.layers):
            # whole-layer remat: the backward keeps a layer's input and recomputes the rest
            fn = jax.checkpoint(lambda p, x, i=i: KimiLinear.layer_apply(config, i, p, x))
            with jax.named_scope("block"):
                x, stats = fn(layer, x)
            if stats is not None:
                all_stats.append(stats)
        with jax.named_scope("final_norm"):
            x = rms_norm(x.astype(jnp.float32), params.final_norm.astype(jnp.float32), config.rms_norm_eps).astype(x.dtype)
        if not return_stats:
            return x
        if not all_stats:
            z = jnp.zeros((), jnp.int32)
            return x, {"counts": jnp.zeros((0, config.n_experts_held), jnp.int32), "dropped": z, "overflowed": z}
        return x, {
            "counts": jnp.stack([s["counts"] for s in all_stats]),
            "dropped": sum(s["dropped"] for s in all_stats),
            "overflowed": sum(s["overflowed"].astype(jnp.int32) for s in all_stats),
        }

    @staticmethod
    def route_stats(config: KimiLinearConfig, params: KimiLinearParams, tokens: Array) -> tp.Dict[str, Array]:
        """The train loop's MoE counters for one microbatch (B, T), forward
        only: `moe.tokens` (B * T), `moe.assignments_here` (token-expert pairs
        of those tokens routed to experts held here, all layers),
        `moe.load_max_over_mean` (over the experts held, worst layer),
        `moe.dropped` (pairs assigned here and not computed: 0),
        `moe.overflowed` (routed layers whose pairs did not fit the dispatch
        buffer and took the exact path, ~10x the expert time: 0 in a healthy
        step)."""
        _, s = KimiLinear.hidden(config, params, tokens, return_stats=True)
        counts = s["counts"].astype(jnp.float32)
        load = jnp.max(counts, axis=-1) / jnp.maximum(jnp.mean(counts, axis=-1), 1.0)
        return {
            "moe.tokens": jnp.asarray(tokens.size, jnp.int32),
            "moe.assignments_here": jnp.sum(s["counts"]),
            "moe.load_max_over_mean": jnp.max(load) if load.size else jnp.zeros(()),
            "moe.dropped": s["dropped"],
            "moe.overflowed": s["overflowed"],
        }

    @staticmethod
    def param_specs(config, tree, mesh):
        """Placement rule: every leaf replicated. The data-parallel mesh is
        the only one this family is wired to (KimiLinearConfig.check_experiment)."""
        del config, mesh
        return jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree)

    @staticmethod
    def flops_per_token(config: KimiLinearConfig, seq_len: tp.Optional[int] = None,
                        stats: tp.Optional[dict] = None) -> float:
        """Training FLOPs a token (forward + backward = 3 x forward), of what
        is computed HERE: 6 x the parameters a token multiplies (a routed
        expert once per token-expert pair routed to an expert held here: as
        `stats`, what `route_stats` returned, counted them; default the
        balanced share top_k * held / n_experts a layer), MLA's causal scores
        and values at 192 / 128 channels, and the KDA recurrence (per token and
        head three products of d_k x d_v: decayed-state read, rank-one write,
        read-out). Recomputed operations (remat) do not count."""
        c = config
        T = seq_len or c.block_size
        D, H, d, r = c.n_embd, c.n_head, c.kda_head_dim, c.kda_gate_rank
        kda = 3 * H * d * D + D * H * d + 2 * (r * D + H * d * r) + H * D
        mla = (H * c.qk_head_dim * D + (c.kv_lora_rank + c.qk_rope_head_dim) * D
               + H * (c.qk_nope_head_dim + c.v_head_dim) * c.kv_lora_rank + D * H * c.v_head_dim)
        expert = 3 * D * c.expert_width
        n_moe = sum(c.mlp_kind(i) == "moe" for i in range(c.n_layer))
        if stats is None:
            assignments_here = n_moe * c.moe_top_k * c.n_experts_held / c.n_experts
        else:
            assignments_here = stats["moe.assignments_here"] / stats["moe.tokens"]
        matmul = c.vocab_size * D + assignments_here * expert  # the embedding is a gather: the head only
        other = 0.0
        for i in range(c.n_layer):
            if c.mixer_kind(i) == "kda":
                matmul += kda
                other += 3 * 2 * H * d * d
            else:
                matmul += mla
                other += 2 * H * (c.qk_head_dim + c.v_head_dim) * T / 2
            if c.mlp_kind(i) == "dense":
                matmul += 3 * D * c.dense_width
            else:
                matmul += c.n_experts * D + c.n_shared_experts * expert
        return 6.0 * matmul + 3.0 * other
