"""Granite-4.0-H: Mamba-2 state-space layers, nine to every grouped-query
attention layer, under the family's four multipliers. A mamba layer keeps no
keys: its memory is a STATE a request, a float32 (P, N) matrix a head (64 heads
of 64 x 128: 2 MB a layer) and the last `mamba_conv - 1` inputs of a short
convolution, whatever the context. The attention layers are grouped-query
attention with NO position signal over the GPT pool's paged layout. SERVED
(sample.py, ServeEngine) over one paged kind and a STATE kind (`cache_kinds`,
sampling/pages.py "State kinds"; models/gpt.py `ServeCache`: `pools` = ((K,
V),) and `state` = (SSM states, convolution histories)), the second family
with one after models/olmo_hybrid.py; training is refused by name.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json
(`model_type: granitemoehybrid`: 40 layers, hidden 2,048, `layer_types` with
`attention` at 5, 15, 25, 35 and `mamba` elsewhere; `mamba_n_heads` 64 x
`mamba_d_head` 64, `mamba_d_state` 128, `mamba_n_groups` 1, `mamba_d_conv` 4
with a bias, `mamba_chunk_size` 256; 32 query and 8 K/V heads of 64,
`position_embedding_type` nope; a gated MLP of 8,192 after every mixer
(`num_local_experts` 0); vocabulary 100,352, tied; RMSNorm eps 1e-5). What the
source does not state is listed, each with its reason, under `assumed` in
benchmarks/configs/granite_4_0_h_micro.json; the float32 reference beside it
follows the same equations and imports nothing from here.

With n(x; g) = g * x / sqrt(mean(x^2) + eps), on the residual stream x (T, D):

    x_0    = embedding_multiplier * E[token]
    h      = x + residual_multiplier * mixer(n(x; g1))
    y      = h + residual_multiplier * W_down(silu(W_gate n(h; g2)) * W_up n(h; g2))
    logits = n(y_last; g_f) E^T / logits_scaling              # the head IS the embedding

`mamba` (Mamba-2; H heads of P channels, N = d_state, ONE group):

    z, xBC, dt = W_z u, W_xbc u, W_dt u                       # H P | H P + 2 N | H, no bias
    xBC    = silu(conv(xBC) + b_c)                            # causal depthwise, 4 taps, the last on the current token
    x, B, C = xBC split                                       # (H, P) | N | N: ONE B and ONE C for all heads
    dt     = softplus(dt + dt_bias)                           # a head, float32, no clamp
    h_t    = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,  A = -exp(A_log)     # (P, N) float32 a head
    y_t    = h_t C_t + D x_t                                  # D a head
    out    = W_out n_HP(y * silu(z); g_n)                     # the gate BEFORE the norm, one norm over all H P channels

which is ops/ssd.py: `ssd_step` a decode step, `ssd_chunked` a prefill chunk
(the slot's state in and out), in chunks of `mamba_chunk`. `attention`: q, k,
v, o without bias, `n_head` query heads on `n_kv_head` K/V heads, NO rotary and
no other position signal, causal softmax of `attention_multiplier` q k^T (1/64
as published, NOT head_dim^-1/2: the paged template is called with the scale).

The parameters are STACKED over the layers of a kind and every forward is one
rolled loop over PERIODS (a period: `attn_at` mamba layers, the attention
layer, the mamba layers after it up to the next period's first), the mamba
layers before and after the attention layer each a rolled loop of their own,
so a program holds two mamba layers and one attention layer whatever the depth.
A layer's slice of a stacked leaf is taken where it is used (`_Layer`: the
matrix's read, fused into the product). The K/V pools (one cache layer an
attention layer) ride the loops' carry, and so do the state arrays in a prefill
program; a decode step's loops only READ them and a loop after the layers'
writes them (`decode_step_paged`). A layer addresses its row by a traced index
and nothing slices a layer out.
"""

from __future__ import annotations

import dataclasses
import math
import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.models.gpt import GPT, CacheKind, ServeCache, StateKind, _paged_write
from midgpt_tpu.models.olmo_hybrid import _linear, _norm, _put_rows, _rows_of  # the state kind's row helpers, the seeded matrix, the float32 norm
from midgpt_tpu.ops.moe import swiglu
from midgpt_tpu.ops.ssd import ssd_chunked, ssd_step_terms, ssd_step_write
from midgpt_tpu.utils.pytree import pytree_dataclass

Array = jax.Array
KeyArray = jax.Array

FAMILY = "granite_hybrid"
GLOBAL, STATE = "global", "ssm_state"
MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Model shape, by the published keys' meaning. The first five fields are
    named as `GPTConfig` names them (models/__init__.py)."""

    block_size: int  # serving cap on prompt + output (max_position_embeddings 131,072: nothing rotates, so no table)
    vocab_size: int
    n_layer: int  # num_hidden_layers run: `layer_types` is read to its first n_layer entries
    n_head: int  # num_attention_heads (query heads of an attention layer)
    n_embd: int  # hidden_size
    n_kv_head: int = 8  # num_key_value_heads
    layer_types: tp.Tuple[str, ...] = ((MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4) * 4  # as published, whole
    mamba_heads: int = 64  # mamba_n_heads
    mamba_head_dim: int = 64  # mamba_d_head (heads x head_dim = mamba_expand x hidden_size)
    mamba_state: int = 128  # mamba_d_state
    mamba_groups: int = 1  # mamba_n_groups: ONE B and ONE C for all heads
    mamba_conv: int = 4  # mamba_d_conv, with a bias (mamba_conv_bias)
    mamba_chunk: int = 256  # mamba_chunk_size
    dense_width: int = 8192  # shared_intermediate_size
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    family: str = FAMILY  # discriminates model_config in config.json

    def __post_init__(self):
        if self.family != FAMILY:
            raise ValueError(f"family={self.family!r} is not {FAMILY!r}")
        object.__setattr__(self, "layer_types", tuple(self.layer_types))  # a list, from config.json
        if self.n_embd % self.n_head or self.n_head % self.n_kv_head:
            raise ValueError(f"n_embd={self.n_embd}, n_head={self.n_head}, n_kv_head={self.n_kv_head}: heads of one width, "
                             "query heads a multiple of the K/V heads")
        if self.mamba_groups != 1:
            raise ValueError(f"mamba_groups={self.mamba_groups}: ops/ssd.py shares ONE B and ONE C among the heads")
        run = self.layer_types[: self.n_layer]
        if len(run) < self.n_layer or set(run) - {MAMBA, ATTENTION} or ATTENTION not in run:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers of {sorted(set(self.layer_types))}; n_layer={self.n_layer}")
        p, a = self.period, self.attn_at
        if self.n_layer % p or run != ((MAMBA,) * a + (ATTENTION,) + (MAMBA,) * (p - 1 - a)) * (self.n_layer // p):
            raise ValueError(
                f"the first {self.n_layer} layer_types are not whole periods of {p} layers with the attention layer at "
                f"index {a} of each: the stack is one rolled loop over periods"
            )

    # -- what the runtime reads of any model config (models/__init__.py) --
    def model(self):
        return GraniteHybrid

    def check_experiment(self, config) -> None:
        m = config.mesh
        over = {a: getattr(m, a) for a in ("fsdp", "sp", "tp", "pp", "ep") if getattr(m, a) not in (1, -1)}
        if over or config.shard_model:
            raise ValueError(f"{FAMILY}: no mesh axis but data is wired (got {over or 'shard_model=True'})")
        if config.spec_layers:
            raise ValueError(f"{FAMILY}: spec_layers needs a verify step that snapshots the SSM state, which is not wired")

    def check_training(self, who: str) -> None:
        raise NotImplementedError(
            f"{who} cannot train a {FAMILY} model: no backward through its stack is wired (the convolution's history, "
            "the period loop and the tied head under its multipliers have none), and at 16 B a parameter one period with "
            "an eighth of the vocabulary is 12.4 GB of state on a 16 GB chip. Serve it: sample.py --engine=continuous, "
            "ServeEngine."
        )

    def check_serving(self, who: str) -> None:
        """sample.py's continuous engine and ServeEngine serve this family."""

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def attn_at(self) -> int:
        """Mamba layers of a period before its attention layer."""
        return self.layer_types.index(ATTENTION)

    @property
    def period(self) -> int:
        """Layers from one attention layer to the next."""
        later = [i for i, t in enumerate(self.layer_types[: self.n_layer]) if t == ATTENTION][1:]
        return later[0] - self.attn_at if later else self.n_layer

    @property
    def n_periods(self) -> int:
        return self.n_layer // self.period

    @property
    def n_mamba(self) -> int:
        return self.n_periods * (self.period - 1)

    @property
    def mamba_inner(self) -> int:
        """Channels of a mamba layer's x, z and y: heads x head_dim."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        """Channels the short convolution runs over: x | B | C side by side."""
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_state

    def state_shapes(self, dtype) -> tp.Tuple[tp.Tuple[tp.Tuple[int, ...], tp.Any], ...]:
        """((shape, dtype), ...) of ONE slot's state row: the SSM state of every
        mamba layer in float32, each head's (P, N) (whole (8, 128) tiles: 2 MB a
        layer as laid out, as published); and the convolution's history, the
        last mamba_conv - 1 inputs of its x | B | C channels ONE AFTER THE
        OTHER IN ONE ROW in the cache's dtype (kept as (taps, channels) the
        bfloat16 minor tile of (16, 128) pads three taps to sixteen: 5.0 MB a
        slot for 0.94)."""
        return (((self.n_mamba, self.mamba_heads, self.mamba_head_dim, self.mamba_state), jnp.float32),
                ((self.n_mamba, (self.mamba_conv - 1) * self.conv_channels), dtype))


@pytree_dataclass
class MambaLayerParams:
    """A mamba layer and its MLP; in `GraniteHybridParams.mamba` every leaf is
    stacked (n_mamba, ...), in layer order. `in_proj` as published is ONE matrix
    of H P + (H P + 2 N) + H rows; it is held as its three row blocks, so that
    no product's output is cut at a lane that is no multiple of 128."""

    norm_in: Array  # (D,) float32, on the mixer's input
    w_z: Array  # (H P, D) the gate
    w_xbc: Array  # (H P + 2 N, D) what the convolution runs over
    w_dt: Array  # (H, D)
    conv: Array  # (H P + 2 N, mamba_conv): taps of the x | B | C channels, the last on the current token
    conv_bias: Array  # (H P + 2 N,)
    a_log: Array  # (H,) float32
    d_skip: Array  # (H,) float32
    dt_bias: Array  # (H,) float32
    gate_norm: Array  # (H P,) float32: one norm over all channels, after the gate
    w_out: Array  # (D, H P)
    norm_mlp: Array  # (D,) float32, on the MLP's input
    w_gate: Array  # (F, D)
    w_up: Array  # (F, D)
    w_down: Array  # (D, F)


@pytree_dataclass
class AttnLayerParams:
    """An attention layer and its MLP; leaves stacked (n_periods, ...)."""

    norm_in: Array
    wq: Array  # (n_head * head_dim, D)
    wk: Array  # (n_kv_head * head_dim, D)
    wv: Array
    wo: Array  # (D, n_head * head_dim)
    norm_mlp: Array
    w_gate: Array
    w_up: Array
    w_down: Array


@pytree_dataclass
class GraniteHybridParams:
    wte: Array  # (V, D): the embedding AND the head (tie_word_embeddings)
    mamba: MambaLayerParams
    attn: AttnLayerParams
    final_norm: Array  # (D,)


# `init` draws the embedding at this standard deviation: the head is the embedding, so it sets the logits' scale
# (sqrt(D) * std / logits_scaling = 0.57 at the published sizes: logits a softmax at temperature 0.8 can tell apart,
# where 1 / sqrt(D) gives 0.125) and, times embedding_multiplier, the stream's (RMS 1.2). Every branch reads the
# stream through a norm, so nothing else depends on it.
WTE_INIT_STD = 0.1

# Rows of a prefill chunk one call of the multi-row paged attention takes, TIMES the query heads a pool head (the
# template folds the group into the rows and keeps a visible-key count in a scalar each): Mosaic refuses 512 of them
# in one kernel (models/olmo_hybrid.py PREFILL_ATTN_ROWS), so a chunk of 512 at four query heads a pool head is
# sixteen calls of 32 rows an attention layer, each sweeping the slot's pages up to its own last row's count.
PREFILL_ATTN_FOLDED_ROWS = 128

_F32_LEAVES = ("norm_in", "norm_mlp", "a_log", "d_skip", "dt_bias", "gate_norm", "final_norm")


class _Layer:
    """Layer `l` (a traced index) of a stacked parameter group: `p.w_gate` is
    that layer's slice of the leaf, taken at the point of use (module docstring)."""

    def __init__(self, stacked, l):
        self._stacked, self._l = stacked, l
        self.dtype = stacked.w_down.dtype  # what the matrices multiply in

    def __getattr__(self, name):
        return jax.lax.dynamic_index_in_dim(getattr(self._stacked, name), self._l, 0, keepdims=False)


def _mlp(c: GraniteHybridConfig, p, x: Array) -> Array:
    with jax.named_scope("dense_ffn"):
        return x + c.residual_multiplier * swiglu(_norm(c, x, p.norm_mlp, p.dtype), p.w_gate, p.w_up, p.w_down).astype(x.dtype)


def _project(p: MambaLayerParams, u: Array) -> tp.Tuple[Array, Array, Array]:
    """(z (..., H P), xBC (..., H P + 2 N) before the convolution, dt (..., H) before its bias) of u (..., D)."""
    return tuple(jnp.einsum("...d,ed->...e", u, w) for w in (p.w_z, p.w_xbc, p.w_dt))


def _conv(p: MambaLayerParams, window: Array, T: int) -> Array:
    """silu of the causal convolution and its bias over `window` (B, K - 1 + T,
    channels): the K - 1 inputs before the T tokens, then them."""
    K = p.conv.shape[-1]
    taps = p.conv.astype(jnp.float32)
    y = sum(window[:, j : j + T].astype(jnp.float32) * taps[:, j] for j in range(K)) + p.conv_bias.astype(jnp.float32)
    return jax.nn.silu(y).astype(window.dtype)


def _split_xbc(c: GraniteHybridConfig, y: Array) -> tp.Tuple[Array, Array, Array]:
    """The convolved channels (..., H P + 2 N) as x (..., H, P), B (..., N), C (..., N)."""
    x, B, C = jnp.split(y, [c.mamba_inner, c.mamba_inner + c.mamba_state], axis=-1)
    return x.reshape(*x.shape[:-1], c.mamba_heads, c.mamba_head_dim), B, C


def _step_size(p: MambaLayerParams, dt: Array) -> Array:
    """softplus(dt + dt_bias), float32, no clamp (time_step_limit (0, inf))."""
    return jax.nn.softplus(dt.astype(jnp.float32) + p.dt_bias.astype(jnp.float32))


def _gated_out(c: GraniteHybridConfig, p: MambaLayerParams, z: Array, y: Array) -> Array:
    """W_out n(y * silu(z); g_n): y (..., H, P) float32, z (..., H P). The gate comes BEFORE the norm."""
    g = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
    return jnp.einsum("...e,de->...d", _norm(c, g, p.gate_norm, p.dtype), p.w_out)


def _qkv(c: GraniteHybridConfig, p: AttnLayerParams, u: Array):
    """q (..., n_head, C), k, v (..., n_kv_head, C) of u (..., D): no bias, no norm, no rotary."""
    proj = lambda w, h: jnp.einsum("...d,ed->...e", u, w).reshape(*u.shape[:-1], h, c.head_dim)
    return proj(p.wq, c.n_head), proj(p.wk, c.n_kv_head), proj(p.wv, c.n_kv_head)


def _paged_rows(c: GraniteHybridConfig, q: Array, ck: Array, cv: Array, table: Array, counts: Array, impl: str,
                layer: Array, split_k: int = 1) -> Array:
    """The paged attention of q (B, R, n_head, C), row r of slot b over its
    first counts[b, r] keys, at the PUBLISHED scale `attention_multiplier`. On
    the kernel path the template takes the scale (left to its default it would
    score at head_dim^-1/2: eight times the published 1/64); the gather
    lowering has no such argument and scores at head_dim^-1/2, so q meets it
    times attention_multiplier * head_dim^1/2. Returns (B, R, n_head, C)."""
    if impl == "kernel":
        from midgpt_tpu.kernels.attention_template import paged_attention_template

        return paged_attention_template(q.transpose(0, 2, 1, 3), ck, cv, table, counts, split_k=split_k, layer=layer,
                                        scale=c.attention_multiplier).transpose(0, 2, 1, 3)
    from midgpt_tpu.kernels.decode_attention import paged_verify_attention

    q = (q.astype(jnp.float32) * (c.attention_multiplier * math.sqrt(c.head_dim))).astype(q.dtype)
    return paged_verify_attention(q, ck, cv, table, counts, impl=impl, split_k=split_k, layer=layer)


class GraniteHybrid:
    """Namespace of pure functions over (GraniteHybridConfig, GraniteHybridParams)."""

    weight_decay_mask = None
    route_stats = None
    verify_step_paged = None  # no speculative verify: a rejected draft would need the state it started from
    prefill_batched = True  # the chunks of a round's slots ride one call; each row's state by its row index
    prefill_rows = staticmethod(GPT.prefill_rows)  # every weight is dense and sees every row
    kernel_sweep_whole = True  # every attention layer's decode attention is this one kernel call

    @staticmethod
    def init(config: GraniteHybridConfig, key: KeyArray) -> GraniteHybridParams:
        """Seeded weights: matrices truncated normal / sqrt(fan_in), taps normal
        / sqrt(taps) with a small bias, every norm gain 1, `A_log` = log U(1,
        16), `dt_bias` = softplus^-1 of dt log-uniform in [1e-3, 1e-1], `D` 1
        (as the open Mamba-2 layer initialises them), the embedding at
        `WTE_INIT_STD`."""
        c = config
        D, F, H, E, Ekv = c.n_embd, c.dense_width, c.mamba_heads, c.n_head * c.head_dim, c.n_kv_head * c.head_dim
        inner, ch = c.mamba_inner, c.conv_channels
        ones = jnp.ones((D,))

        def init_mamba(k: KeyArray) -> MambaLayerParams:
            ks = jax.random.split(k, 11)
            dt = jnp.exp(jax.random.uniform(ks[6], (H,), minval=math.log(1e-3), maxval=math.log(1e-1)))
            return MambaLayerParams(
                norm_in=ones, w_z=_linear(ks[0], inner, D), w_xbc=_linear(ks[1], ch, D), w_dt=_linear(ks[2], H, D),
                conv=jax.random.normal(ks[3], (ch, c.mamba_conv)) / math.sqrt(c.mamba_conv),
                conv_bias=0.1 * jax.random.normal(ks[4], (ch,)),
                a_log=jnp.log(jax.random.uniform(ks[5], (H,), minval=1.0, maxval=16.0)), d_skip=jnp.ones((H,)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                gate_norm=jnp.ones((inner,)), w_out=_linear(ks[7], D, inner), norm_mlp=ones,
                w_gate=_linear(ks[8], F, D), w_up=_linear(ks[9], F, D), w_down=_linear(ks[10], D, F),
            )

        def init_attn(k: KeyArray) -> AttnLayerParams:
            ks = jax.random.split(k, 7)
            return AttnLayerParams(
                norm_in=ones, wq=_linear(ks[0], E, D), wk=_linear(ks[1], Ekv, D), wv=_linear(ks[2], Ekv, D),
                wo=_linear(ks[3], D, E), norm_mlp=ones,
                w_gate=_linear(ks[4], F, D), w_up=_linear(ks[5], F, D), w_down=_linear(ks[6], D, F),
            )

        k_embed, k_mamba, k_attn = jax.random.split(key, 3)
        return GraniteHybridParams(
            wte=WTE_INIT_STD * jax.random.normal(k_embed, (c.vocab_size, D)),
            mamba=jax.vmap(init_mamba)(jax.random.split(k_mamba, c.n_mamba)),
            attn=jax.vmap(init_attn)(jax.random.split(k_attn, c.n_periods)),
            final_norm=ones,
        )

    @staticmethod
    def cast_params(params: GraniteHybridParams, dtype) -> GraniteHybridParams:
        """The compute copy: matrices, taps and the taps' bias in `dtype`; `A_log`, `D`, `dt_bias` and every norm gain as they are."""
        return jax.tree_util.tree_map_with_path(
            lambda path, p: p if str(getattr(path[-1], "name", path[-1])) in _F32_LEAVES
            or not jnp.issubdtype(p.dtype, jnp.floating) else p.astype(dtype),
            params,
        )

    @staticmethod
    def count_params(params: GraniteHybridParams) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    @staticmethod
    def param_specs(config, tree, mesh):
        del config, mesh  # every leaf replicated: no mesh axis is wired (check_experiment)
        return jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree)

    @staticmethod
    def flops_per_token(config: GraniteHybridConfig, seq_len: tp.Optional[int] = None, stats=None) -> float:
        """FORWARD FLOPs a token (this family is served, not trained): 2 x the
        parameters a token multiplies (the tied head once), the recurrence's
        write and read of a (P, N) state a head of a mamba layer, and an
        attention layer's scores and values over a causal context."""
        del stats
        c = config
        T = seq_len or c.block_size
        D, F, E, Ekv = c.n_embd, c.dense_width, c.n_head * c.head_dim, c.n_kv_head * c.head_dim
        mamba = D * (2 * c.mamba_inner + 2 * c.mamba_state + c.mamba_heads) + c.conv_channels * c.mamba_conv \
            + c.mamba_inner * D + 2 * c.mamba_inner * c.mamba_state
        attn = 2 * D * E + 2 * D * Ekv + 2 * E * T / 2
        return 2.0 * (c.n_mamba * mamba + c.n_periods * attn + c.n_layer * 3 * D * F + c.vocab_size * D)

    # ------------------------------------------------------------------
    # the stack: what every forward shares
    # ------------------------------------------------------------------

    @staticmethod
    def _run(c: GraniteHybridConfig, params: GraniteHybridParams, x: Array, carry, mamba_mix, attn_mix):
        """Every period applied to x (..., D) float32. `mamba_mix(carry, l, p,
        u) -> (out, carry)` is the caller's mixer for mamba layer `l` (a traced
        index into the state arrays) with parameters `p` over the NORMED stream
        `u` in the matrices' dtype; `attn_mix(carry, row, p, u)` the same for
        the attention layer of cache layer `row`. `carry` (pools, states, or
        None) rides the loops. Returns (x, carry)."""
        m, before = c.period - 1, c.attn_at

        def mamba_layer(l, state):
            x, carry = state
            p = _Layer(params.mamba, l)
            with jax.named_scope("attn_linear"):
                o, carry = mamba_mix(carry, l, p, _norm(c, x, p.norm_in, p.dtype))
                x = x + c.residual_multiplier * o.astype(x.dtype)
            return _mlp(c, p, x), carry

        def period(i, state):
            state = jax.lax.fori_loop(0, before, lambda j, s: mamba_layer(i * m + j, s), state)
            x, carry = state
            p = _Layer(params.attn, i)
            with jax.named_scope("attn_global"):
                o, carry = attn_mix(carry, i, p, _norm(c, x, p.norm_in, p.dtype))
                x = x + c.residual_multiplier * o.astype(x.dtype)
            state = _mlp(c, p, x), carry
            return jax.lax.fori_loop(before, m, lambda j, s: mamba_layer(i * m + j, s), state)

        return jax.lax.fori_loop(0, c.n_periods, period, (x, carry))

    @staticmethod
    def _embed(c: GraniteHybridConfig, params: GraniteHybridParams, tokens: Array) -> Array:
        with jax.named_scope("embed"):
            return c.embedding_multiplier * jnp.take(params.wte, tokens, axis=0).astype(jnp.float32)

    @staticmethod
    def _head(c: GraniteHybridConfig, params: GraniteHybridParams, x: Array) -> Array:
        """n(x; g_f) E^T / logits_scaling: the embedding read once more, as the head."""
        with jax.named_scope("lm_head"):
            return jnp.einsum("...d,vd->...v", _norm(c, x, params.final_norm, params.wte.dtype), params.wte) / c.logits_scaling

    # ------------------------------------------------------------------
    # the plain full forward (tests, sample.py's scoring; no cache)
    # ------------------------------------------------------------------

    @staticmethod
    def hidden(config: GraniteHybridConfig, params: GraniteHybridParams, tokens: Array, *, key=None,
               inference: bool = False, attn_fn=None) -> Array:
        """Whole sequences (B, T) -> the stream before the final norm (B, T, D)."""
        del key, inference
        if attn_fn is not None:
            raise ValueError(f"{FAMILY}: a mesh-bound attn_fn is not wired")
        c = config
        B, T = tokens.shape
        pos = jnp.arange(T)
        keep = pos[None, :] <= pos[:, None]

        def mamba_mix(carry, l, p, u):
            z, xbc, dt = _project(p, u)
            x, Bm, Cm = _split_xbc(c, _conv(p, jnp.pad(xbc, ((0, 0), (c.mamba_conv - 1, 0), (0, 0))), T))
            with jax.named_scope("linear_state"):
                y, _ = ssd_chunked(x, _step_size(p, dt), -jnp.exp(p.a_log), Bm, Cm, p.d_skip, chunk=c.mamba_chunk)
            return _gated_out(c, p, z, y), carry

        def attn_mix(carry, row, p, u):
            q, k, v = _qkv(c, p, u)
            g = c.n_head // c.n_kv_head
            q = q.reshape(B, T, c.n_kv_head, g, c.head_dim)
            s = jnp.einsum("btkgc,bskc->bkgts", q, k).astype(jnp.float32) * c.attention_multiplier
            prob = jax.nn.softmax(jnp.where(keep, s, float("-inf")), axis=-1).astype(v.dtype)
            o = jnp.einsum("bkgts,bskc->btkgc", prob, v).reshape(B, T, -1)
            return jnp.einsum("bte,de->btd", o, p.wo), carry

        return GraniteHybrid._run(c, params, GraniteHybrid._embed(c, params, tokens), None, mamba_mix, attn_mix)[0]

    @staticmethod
    def apply(config: GraniteHybridConfig, params: GraniteHybridParams, tokens: Array) -> Array:
        """Logits (B, T, V) of whole sequences."""
        return GraniteHybrid._head(config, params, GraniteHybrid.hidden(config, params, tokens))

    # ------------------------------------------------------------------
    # serving (sampling/serve.py reaches these through models/__init__.py)
    # ------------------------------------------------------------------

    @staticmethod
    def cache_kinds(config: GraniteHybridConfig):
        """One PAGED kind (the attention layers keep the whole context) and one
        STATE kind: a row a slot, of the shapes `state_shapes` gives."""
        return (CacheKind(GLOBAL, 0, 0), StateKind(STATE, config.state_shapes))

    @staticmethod
    def init_cache(config: GraniteHybridConfig, num_pages: tp.Sequence[int], page_size: int = 8,
                   dtype=jnp.bfloat16, kernel_layout: bool = False) -> ServeCache:
        """Zeroed K and V pools of `num_pages[0]` pages, `n_kv_head` pool heads
        and one cache layer an attention layer, and the state kind's arrays at
        `num_pages[1]` rows: the SSM states (n_mamba, rows, H, P, N) float32 and
        the convolution's history (n_mamba, rows, (mamba_conv - 1) * channels).
        Counter: `ssm_counts` (3,) int32, the decoded tokens, prefilled tokens
        and prefill chunks the mamba layers have taken."""
        c = config
        return ServeCache.zeros(FAMILY, (((c.n_periods, c.n_kv_head, c.head_dim),) * 2,), num_pages, page_size, dtype,
                                kernel_layout, (jnp.zeros((3,), jnp.int32),), state_kind=GraniteHybrid.cache_kinds(c)[1])

    @staticmethod
    def kernel_sweep(config: GraniteHybridConfig, cache: ServeCache):
        """(pool shape, q rows a pool head, window, sinks) of the decode kernel's sweep."""
        return cache.pools[0][0].shape, config.n_head // config.n_kv_head, 0, 0

    @staticmethod
    def serve_counters(config: GraniteHybridConfig, cache: ServeCache) -> tp.Dict[str, float]:
        """`ssm.decode_tokens` (one-token updates of an active slot, a layer
        counted once), `ssm.prefill_tokens` / `ssm.prefill_chunks` (tokens and
        slot-chunks the chunk-carrying scan has taken), and what the K/V pools
        keep of a token over the attention layers, in bytes as laid out."""
        n = [int(x) for x in jax.device_get(cache.counters[0])]
        return {"ssm.decode_tokens": n[0], "ssm.prefill_tokens": n[1], "ssm.prefill_chunks": n[2],
                f"kv.{GLOBAL}_bytes_per_token": sum(a.nbytes for a in cache.pool_arrays()) / (cache.num_pages * cache.page_size)}

    @staticmethod
    def decode_step_paged(config: GraniteHybridConfig, params: GraniteHybridParams, token: Array, cache: ServeCache,
                          page_table, lengths: Array, active: Array,
                          attn_impl: str = "auto", mesh=None, split_k: int = 1) -> tp.Tuple[Array, ServeCache]:
        """One decode step for the B slots at B positions (GPT.decode_step_paged's
        contract). `page_table` is (the global kind's (B, pages), the state rows
        (B,)); the batch IS the slots in order and a slot's row is the row of
        its index (`PagePool.tables` refuses any other), so the one-token update
        (ops/ssd.py `ssd_step`, in its two halves) runs over rows [0, B) where
        they lie and the row vector is not read: no gather, no scatter. The
        layers' loop only READS the state arrays (`ssd_step_terms`: a layer's
        output needs the states as they came) and keeps each layer's terms (38
        MB at 64 slots); a loop of its own after it WRITES every layer's rows
        (`ssd_step_write`) and histories. A program
        that commits no state (`ServeEngine.next_logits`: sampling/pages.py
        `keep_state` hands the rows back as they came) therefore holds no
        write at all; with the write inside the layers' loop the compiler kept
        a COPY of the whole state array beside it (4.9 GB at 65 rows: PERF.md
        section 6 PR 63). An ACTIVE slot's state and convolution history
        advance by its token; an inactive slot's stay bit for bit (it may be in
        the middle of its chunked prefill), and it writes no key. Returns
        (logits (B, V), cache)."""
        from midgpt_tpu.kernels.decode_attention import resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        table, _ = page_table
        B = token.shape[0]
        ps, pos = cache.page_size, lengths
        counts = jnp.maximum(active.astype(jnp.int32) * (pos + 1), 1)
        write_pages = jnp.where(active, jnp.take_along_axis(table, (pos // ps)[:, None], axis=1)[:, 0], cache.num_pages)
        offs = pos % ps

        S, hist = cache.state  # READ in the layers' loop, written after it (docstring)
        f32 = jnp.float32
        H, P, N = c.mamba_heads, c.mamba_head_dim, c.mamba_state
        terms = (jnp.zeros((c.n_mamba, B, H), f32), jnp.zeros((c.n_mamba, B, H, P), f32), jnp.zeros((c.n_mamba, B, N), f32),
                 jnp.zeros((c.n_mamba, B, hist.shape[-1]), hist.dtype))  # each layer's decay, dt x, B and next history
        put = lambda a, l, v: jax.lax.dynamic_update_slice(a, v[None].astype(a.dtype), (l,) + (0,) * v.ndim)

        def mamba_mix(carry, l, p, u):  # u (B, D)
            pools, (decays, wrotes, Bs, hists) = carry
            z, xbc, dt = _project(p, u)
            h0 = jax.lax.dynamic_index_in_dim(hist, l, 0, keepdims=False)[:B].reshape(B, -1, xbc.shape[-1])  # (B, K - 1, channels)
            window = jnp.concatenate([h0, xbc[:, None].astype(h0.dtype)], axis=1)
            x, Bm, Cm = _split_xbc(c, _conv(p, window, 1)[:, 0])
            with jax.named_scope("linear_state"):
                S0 = jax.lax.dynamic_index_in_dim(S, l, 0, keepdims=False)[:B]
                y, decay, wrote = ssd_step_terms(x, _step_size(p, dt), -jnp.exp(p.a_log), Bm, Cm, p.d_skip, S0)
            kept = (put(decays, l, decay), put(wrotes, l, wrote), put(Bs, l, Bm), put(hists, l, window[:, 1:].reshape(B, -1)))
            return _gated_out(c, p, z, y), (pools, kept)

        def attn_mix(carry, row, p, u):
            pools, state = carry
            q, k, v = _qkv(c, p, u)
            ck, cv, _, _ = _paged_write((*pools, None, None), row, write_pages, offs, k, v, attn_impl, None)
            o = _paged_rows(c, q[:, None], ck, cv, table, counts[:, None], attn_impl, row, split_k)[:, 0]
            return jnp.einsum("be,de->bd", o.astype(p.dtype).reshape(B, -1), p.wo), ((ck, cv), state)

        x, ((ck, cv), (decays, wrotes, Bs, hists)) = GraniteHybrid._run(
            c, params, GraniteHybrid._embed(c, params, token), (cache.pools[0], terms), mamba_mix, attn_mix)

        def write(l, state):  # layer l's rows [0, B) read and written once, where they lie
            S, hist = state
            S0 = jax.lax.dynamic_index_in_dim(S, l, 0, keepdims=False)[:B]
            S1 = ssd_step_write(S0, decays[l], wrotes[l], Bs[l])
            h0 = jax.lax.dynamic_index_in_dim(hist, l, 0, keepdims=False)[:B]
            return (jax.lax.dynamic_update_slice(S, jnp.where(active[:, None, None, None], S1, S0)[None], (l, 0, 0, 0, 0)),
                    jax.lax.dynamic_update_slice(hist, jnp.where(active[:, None], hists[l], h0)[None], (l, 0, 0)))

        with jax.named_scope("attn_linear"), jax.named_scope("linear_state"):
            S, hist = jax.lax.fori_loop(0, c.n_mamba, write, (S, hist))
        counted = cache.counters[0].at[0].add(jnp.sum(active.astype(jnp.int32)))
        return GraniteHybrid._head(c, params, x), ServeCache(pools=((ck, cv),), state=(S, hist), counters=(counted,))

    @staticmethod
    def prefill_paged_chunk(config: GraniteHybridConfig, params: GraniteHybridParams, tokens: Array, start: Array,
                            n_valid: Array, cache: ServeCache, page_table,
                            attn_impl: str = "auto", mesh=None) -> tp.Tuple[Array, ServeCache]:
        """The prompt chunks of B requests, row b's being [start[b], start[b] +
        n_valid[b]) (GPT.prefill_paged_chunk's contract). `page_table` is (the
        global kind's (B, pages), the state rows (B,)): row b's SSM state and
        convolution history are READ from state row `rows[b]` (what its chunk
        before left) unless its chunk is the prompt's first (start 0), which
        begins from ZEROS whatever the row holds: that is the row's reset
        (models/__init__.py, "a state kind"); the state after its n_valid
        tokens is WRITTEN back; tokens past n_valid change neither (dt = 0: no
        decay, no write; the history is cut at n_valid). An empty place
        (n_valid 0) names the sink row. The attention layers write their K/V,
        then each row attends through the multi-row paged attention under its
        own counts. Returns (logits of each row's last valid position (B, V),
        cache); the ONE-ROW call (scalar `start` / `n_valid`) returns (1, 1, V)."""
        from midgpt_tpu.kernels.decode_attention import resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        table, rows = page_table
        one_row = jnp.ndim(start) == 0
        start, n_valid, rows = jnp.reshape(start, (-1,)), jnp.reshape(n_valid, (-1,)), jnp.reshape(rows, (-1,))
        B, T = tokens.shape
        K1 = c.mamba_conv - 1
        ps = cache.page_size
        t_idx = jnp.arange(T, dtype=jnp.int32)
        positions = start[:, None] + t_idx
        valid = t_idx < n_valid[:, None]  # (B, T)
        write_pages = jnp.where(valid, jnp.take_along_axis(table, positions // ps, axis=1), cache.num_pages)
        offs = positions % ps
        # row t sees start + t + 1 keys; pad rows what the last valid row sees, an empty row one key
        counts = jnp.maximum(jnp.minimum(positions, (start + n_valid)[:, None] - 1) + 1, 1)
        attn_rows = max(1, PREFILL_ATTN_FOLDED_ROWS // (c.n_head // c.n_kv_head))

        def carried(a, l):  # row b's slice of a state array, zeros where its prompt starts here
            a = _rows_of(a, l, rows)
            return jnp.where(jnp.reshape(start == 0, (B,) + (1,) * (a.ndim - 1)), jnp.zeros((), a.dtype), a)

        def mamba_mix(carry, l, p, u):  # u (B, T, D)
            pools, (S, hist) = carry
            z, xbc, dt = _project(p, u)
            window = jnp.concatenate([carried(hist, l).reshape(B, K1, -1), xbc.astype(hist.dtype)], axis=1)  # (B, K - 1 + T, channels)
            x, Bm, Cm = _split_xbc(c, _conv(p, window, T))
            with jax.named_scope("linear_state"):
                y, S1 = ssd_chunked(x, jnp.where(valid[..., None], _step_size(p, dt), 0.0), -jnp.exp(p.a_log), Bm, Cm,
                                    p.d_skip, carried(S, l), chunk=c.mamba_chunk)
                S = _put_rows(S, l, rows, S1)
            # the inputs at [n_valid - (K - 1), n_valid): the window's rows n_valid .. n_valid + K - 2
            keep = jnp.take_along_axis(window, (n_valid[:, None] + jnp.arange(K1))[:, :, None], axis=1)
            return _gated_out(c, p, z, y), (pools, (S, _put_rows(hist, l, rows, keep.reshape(B, -1))))

        def attn_mix(carry, row, p, u):
            pools, state = carry
            q, k, v = _qkv(c, p, u)
            ck, cv, _, _ = _paged_write((*pools, None, None), row, write_pages, offs, k, v, attn_impl, None)
            # the innermost scope names the custom calls `prefill_attn.<n>` in the device trace (as
            # GPT.prefill_paged_chunk does), apart from `attn_global`'s decode kernel
            with jax.named_scope("prefill_attn"):
                o = jnp.concatenate([
                    _paged_rows(c, q[:, t : t + attn_rows], ck, cv, table, counts[:, t : t + attn_rows], attn_impl, row)
                    for t in range(0, T, attn_rows)], axis=1)  # (B, T, H, C)
            return jnp.einsum("bte,de->btd", o.astype(p.dtype).reshape(B, T, -1), p.wo), ((ck, cv), state)

        x, ((ck, cv), state) = GraniteHybrid._run(
            c, params, GraniteHybrid._embed(c, params, tokens), (cache.pools[0], cache.state), mamba_mix, attn_mix)
        last = jnp.take_along_axis(x, jnp.maximum(n_valid - 1, 0)[:, None, None], axis=1)  # (B, 1, D)
        logits = GraniteHybrid._head(c, params, last)
        counted = cache.counters[0] + jnp.stack([jnp.zeros((), jnp.int32), jnp.sum(n_valid), jnp.sum((n_valid > 0).astype(jnp.int32))])
        return (logits if one_row else logits[:, 0]), ServeCache(pools=((ck, cv),), state=state, counters=(counted,))
