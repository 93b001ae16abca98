"""Olmo-Hybrid: Gated-DeltaNet layers, three to every full-attention layer.
The linear layers keep no keys: their memory is a STATE a request, a float32
(d_k, d_v) matrix a head and the last `conv_kernel - 1` inputs of a short
convolution, whatever the context. The full layers are plain multi-head
attention over the GPT pool's paged layout. SERVED (sample.py, ServeEngine):
the serving stack's first family with a STATE kind of cache (`cache_kinds`,
sampling/pages.py; models/gpt.py `ServeCache`: `pools` = ((K, V),) and
`state` = (delta-rule states, convolution histories)); training is refused by name (`check_training`).

Source: https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json
(`model_type: olmo_hybrid`: 32 layers, hidden 3,840, `layer_types`
(linear_attention x 3, full_attention) x 8; linear layers of 30 key heads of
96 and 30 value heads of 192, a convolution of 4 taps, `linear_allow_neg_eigval`
true; full layers of 30 heads (3,840 / 30 = 128 channels); SwiGLU 11,008;
vocabulary 100,352, untied; RMSNorm eps 1e-6; `rope_theta` null). What the
source does not state is listed, each with its reason, under `assumed` in
benchmarks/configs/olmo_hybrid_7b_pp2.json; the float32 reference beside it
follows the same equations and imports nothing from here.

With n(x; g) = g * x / sqrt(mean(x^2) + eps), on the residual stream x (T, D):

    h = x + n(mixer(x); g1)            # OLMo-2/3: the norm is on each branch's OUTPUT
    y = h + n(W_down(silu(W_gate h) * W_up h); g2)
    logits = n(y_last; g_f) W_head^T

`linear_attention` (Gated DeltaNet), H heads of d_k keys and d_v values:

    q, k, v = silu(conv(W_q x)), silu(conv(W_k x)), silu(conv(W_v x))   # causal depthwise, 4 taps, no bias
    q, k    = q / |q|_2 * d_k^-1/2, k / |k|_2                           # per head, eps 1e-6
    beta    = 2 sigmoid(W_b x)          (in (0, 2): allow_neg_eigval)   # a head
    g       = -exp(A_log) softplus(W_a x + dt_bias) <= 0, float32       # ONE scalar a head and token
    S'      = exp(g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
    out     = W_o (n_dv(o_t; g_o) * silu(W_g x))                        # a gain of d_v shared by the heads

which is ops/kda.py's recurrence with the per-channel gate broadcast:
`kda_step` a decode step, `kda_chunked` a prefill chunk, the slot's state in
and out. `full_attention`: q, k = n(W_q x; g_q), n(W_k x; g_k) over the WHOLE
n_head * head_dim channels, then split into heads; NO rotary and no other
position signal (`rope_theta` null: the delta-rule layers before each full
layer carry order); causal softmax at head_dim^-1/2; W_o.

The parameters are STACKED over the layers of a kind and every forward is one
rolled loop over periods (a period: the linear layers up to and with the next
full layer), so a program's size does not grow with the depth. A layer's slice
of a stacked leaf is taken where it is used (`_Layer`): it is the matrix's read,
which the compiler fuses into the product (handed to the loop as its scanned
input, a period's matrices were COPIED out of the stack before any was read:
three times the weight traffic, PERF.md section 6 PR 59). The K/V pools (one
cache layer a PERIOD) and the state arrays ride the loop's carry; a layer
addresses its row by a traced index and nothing slices a layer out.
"""

from __future__ import annotations

import dataclasses
import math
import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.models.gpt import GPT, CacheKind, ServeCache, StateKind, _paged_write
from midgpt_tpu.ops.kda import kda_chunked, kda_step
from midgpt_tpu.ops.moe import swiglu
from midgpt_tpu.ops.norms import rms_norm
from midgpt_tpu.utils.pytree import pytree_dataclass

Array = jax.Array
KeyArray = jax.Array

FAMILY = "olmo_hybrid"
GLOBAL, STATE = "global", "gdn_state"
LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """Model shape, by the published keys' meaning. The first five fields are
    named as `GPTConfig` names them (models/__init__.py)."""

    block_size: int  # serving cap on prompt + output (max_position_embeddings 65,536)
    vocab_size: int
    n_layer: int  # num_hidden_layers run: `layer_types` is read to its first n_layer entries
    n_head: int  # num_attention_heads = num_key_value_heads (full layers)
    n_embd: int  # hidden_size
    layer_types: tp.Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 8  # as published, whole
    linear_heads: int = 30  # linear_num_key_heads = linear_num_value_heads
    linear_key_dim: int = 96  # linear_key_head_dim
    linear_value_dim: int = 192  # linear_value_head_dim
    conv_kernel: int = 4  # linear_conv_kernel_dim
    allow_neg_eigval: bool = True  # linear_allow_neg_eigval: beta in (0, 2)
    dense_width: int = 11008  # intermediate_size
    rms_norm_eps: float = 1e-6
    family: str = FAMILY  # discriminates model_config in config.json

    def __post_init__(self):
        if self.family != FAMILY:
            raise ValueError(f"family={self.family!r} is not {FAMILY!r}")
        object.__setattr__(self, "layer_types", tuple(self.layer_types))  # a list, from config.json
        if self.n_embd % self.n_head:
            raise ValueError(f"n_embd={self.n_embd} is not n_head={self.n_head} heads of one width")
        run = self.layer_types[: self.n_layer]
        if len(run) < self.n_layer or set(run) - {LINEAR, FULL}:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers of {sorted(set(self.layer_types))}; n_layer={self.n_layer}")
        p = self.period
        if self.n_layer % p or run != ((LINEAR,) * (p - 1) + (FULL,)) * (self.n_layer // p):
            raise ValueError(
                f"the first {self.n_layer} layer_types are not whole periods of {p - 1} linear layers and a full one: "
                "the stack is one rolled loop over periods"
            )

    # -- what the runtime reads of any model config (models/__init__.py) --
    def model(self):
        return OlmoHybrid

    def check_experiment(self, config) -> None:
        m = config.mesh
        over = {a: getattr(m, a) for a in ("fsdp", "sp", "tp", "pp", "ep") if getattr(m, a) not in (1, -1)}
        if over or config.shard_model:
            raise ValueError(f"{FAMILY}: no mesh axis but data is wired (got {over or 'shard_model=True'})")
        if config.spec_layers:
            raise ValueError(f"{FAMILY}: spec_layers needs a verify step that snapshots the delta-rule state, which is not wired")

    def check_training(self, who: str) -> None:
        raise NotImplementedError(
            f"{who} cannot train a {FAMILY} model: no backward through its stack is wired (the chunked delta rule has "
            "one, kernels/kda.py; the convolution's history, the scalar gate's parameters and the period loop do not), "
            "and at 16 B a parameter one period with an eighth of the vocabulary is 14.9 GB of state on a 16 GB chip. "
            "Serve it: sample.py --engine=continuous, ServeEngine."
        )

    def check_serving(self, who: str) -> None:
        """sample.py's continuous engine and ServeEngine serve this family."""

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def period(self) -> int:
        """Layers from one full layer to the next, that one included."""
        return self.layer_types.index(FULL) + 1

    @property
    def n_periods(self) -> int:
        return self.n_layer // self.period

    @property
    def n_linear(self) -> int:
        return self.n_periods * (self.period - 1)

    @property
    def conv_channels(self) -> int:
        """Channels the short convolution runs over: q, k and v side by side."""
        return self.linear_heads * (2 * self.linear_key_dim + self.linear_value_dim)

    def state_shapes(self, dtype) -> tp.Tuple[tp.Tuple[tp.Tuple[int, ...], tp.Any], ...]:
        """((shape, dtype), ...) of ONE slot's state row: the delta-rule state of
        every linear layer in float32, each head's (d_v, d_k), the one layout
        of a carried state (ops/kda.py, module docstring); and the
        convolution's history (the last conv_kernel - 1 inputs of its q | k | v
        channels, one after the other in one row) in the cache's dtype."""
        return (((self.n_linear, self.linear_heads, self.linear_value_dim, self.linear_key_dim), jnp.float32),
                ((self.n_linear, (self.conv_kernel - 1) * self.conv_channels), dtype))


@pytree_dataclass
class LinearLayerParams:
    """A linear_attention layer and its MLP; in `OlmoHybridParams.linear` every
    leaf is stacked (n_linear, ...), in layer order."""

    wq: Array  # (H * d_k, D)
    wk: Array  # (H * d_k, D)
    wv: Array  # (H * d_v, D)
    conv: Array  # (H * (2 d_k + d_v), conv_kernel): taps of the q | k | v channels, the last on the current token
    w_beta: Array  # (H, D)
    w_a: Array  # (H, D)
    a_log: Array  # (H,) float32
    dt_bias: Array  # (H,) float32
    wg: Array  # (H * d_v, D) output gate
    o_norm: Array  # (d_v,) float32, shared by the heads
    wo: Array  # (D, H * d_v)
    norm_attn: Array  # (D,) on the mixer's output
    w_gate: Array  # (F, D)
    w_up: Array  # (F, D)
    w_down: Array  # (D, F)
    norm_mlp: Array  # (D,) on the MLP's output


@pytree_dataclass
class FullLayerParams:
    """A full_attention layer and its MLP; leaves stacked (n_periods, ...)."""

    wq: Array  # (E, D), E = n_head * head_dim
    wk: Array
    wv: Array
    q_norm: Array  # (E,) float32: over ALL channels, before the split into heads
    k_norm: Array  # (E,)
    wo: Array  # (D, E)
    norm_attn: Array
    w_gate: Array
    w_up: Array
    w_down: Array
    norm_mlp: Array


@pytree_dataclass
class OlmoHybridParams:
    wte: Array  # (V, D)
    linear: LinearLayerParams
    full: FullLayerParams
    final_norm: Array  # (D,)
    lm_head: Array  # (V, D), untied


# `init` seeds W_a at this fraction of a dense matrix's scale. The gate is g = -exp(A_log) softplus(W_a x + dt_bias) with
# A_log and dt_bias drawn as the layer's initialisation draws them (a decay of e^-0.001 to e^-1.6 a token: a memory of
# one to a thousand tokens). A trained W_a keeps a head inside that range; seeded like a dense matrix against a residual
# stream of RMS 1-6 it adds N(0, 1..36) to dt_bias, softplus gives 1-10 and every head forgets within one token: the
# recurrence would carry nothing from chunk to chunk, and a check against the reference could not see a state row left
# dirty, a chunk's carry lost or a step applied twice (tests/test_olmo_hybrid.py holds those three to be SEEN).
W_A_INIT = 0.1

# Rows of a prefill chunk one call of the multi-row paged attention takes (kernels/attention_template.py at n_rows =
# this): the template keeps a row's visible-key count in a scalar each and Mosaic refuses 512 of them in one kernel
# ("Input offsets outside of the first tile"); 128 is the widest the suite compiles for the chip (tests/test_chip_compile.py).
# A chunk of 512 is four calls a full layer, each sweeping the slot's pages up to its own last row's count.
PREFILL_ATTN_ROWS = 128

_F32_LEAVES = ("a_log", "dt_bias", "o_norm", "q_norm", "k_norm", "norm_attn", "norm_mlp", "final_norm")


class _Layer:
    """Layer `l` (a traced index) of a stacked parameter group: `p.wq` is that
    layer's slice of the leaf, taken at the point of use (module docstring)."""

    def __init__(self, stacked, l):
        self._stacked, self._l = stacked, l
        self.dtype = stacked.wq.dtype  # what the matrices multiply in

    def __getattr__(self, name):
        return jax.lax.dynamic_index_in_dim(getattr(self._stacked, name), self._l, 0, keepdims=False)


def _linear(key: KeyArray, out_features: int, in_features: int) -> Array:
    return jax.random.truncated_normal(key, -2.0, 2.0, (out_features, in_features)) / math.sqrt(in_features)


def _norm(c: OlmoHybridConfig, x: Array, w: Array, dtype=None) -> Array:
    """Weighted RMSNorm in float32, handed on in `dtype` (x's own where not given)."""
    return rms_norm(x.astype(jnp.float32), w.astype(jnp.float32), c.rms_norm_eps).astype(dtype or x.dtype)


def _l2(x: Array) -> Array:
    """x / |x|_2 over the last axis, float32 (eps 1e-6 inside the root, as the open layer's)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _mlp(c: OlmoHybridConfig, p, x: Array) -> Array:
    with jax.named_scope("dense_ffn"):
        y = swiglu(x.astype(p.dtype), p.w_gate, p.w_up, p.w_down)
        return x + _norm(c, y, p.norm_mlp, x.dtype)


def _gates(c: OlmoHybridConfig, p: LinearLayerParams, a: Array) -> tp.Tuple[Array, Array]:
    """(g <= 0, beta) a head of `a` (..., D), float32."""
    f32 = jnp.float32
    beta = jax.nn.sigmoid(jnp.einsum("...d,hd->...h", a, p.w_beta).astype(f32))
    dt = jax.nn.softplus(jnp.einsum("...d,hd->...h", a, p.w_a).astype(f32) + p.dt_bias.astype(f32))
    return -jnp.exp(p.a_log.astype(f32)) * dt, beta * (2.0 if c.allow_neg_eigval else 1.0)


def _project(p: LinearLayerParams, a: Array) -> Array:
    """The q | k | v channels of a (..., D) before the convolution, side by side."""
    return jnp.concatenate([jnp.einsum("...d,ed->...e", a, w) for w in (p.wq, p.wk, p.wv)], axis=-1)


def _split_qkv(c: OlmoHybridConfig, y: Array) -> tp.Tuple[Array, Array, Array]:
    """The convolved channels (..., H (2 d_k + d_v)) as q, k (..., H, d_k) normalised, v (..., H, d_v)."""
    H, dk, dv = c.linear_heads, c.linear_key_dim, c.linear_value_dim
    q, k, v = jnp.split(y, [H * dk, 2 * H * dk], axis=-1)
    heads = lambda a, d: a.reshape(*a.shape[:-1], H, d)
    return (_l2(heads(q, dk)) * dk**-0.5).astype(y.dtype), _l2(heads(k, dk)).astype(y.dtype), heads(v, dv)


def _gated_out(c: OlmoHybridConfig, p: LinearLayerParams, a: Array, o: Array) -> Array:
    """W_o (n(o; g_o) * silu(W_g a)): o (..., H, d_v) float32, a (..., D)."""
    z = jnp.einsum("...d,ed->...e", a, p.wg)
    o = _norm(c, o, p.o_norm, jnp.float32).reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
    return jnp.einsum("...e,de->...d", o.astype(p.dtype), p.wo)


def _qkv_full(c: OlmoHybridConfig, p: FullLayerParams, a: Array):
    """q, k (normed over all channels), v of a (..., D), each (..., H, C)."""
    heads = lambda y: y.reshape(*y.shape[:-1], c.n_head, c.head_dim)
    proj = lambda w: jnp.einsum("...d,ed->...e", a, w)
    return heads(_norm(c, proj(p.wq), p.q_norm)), heads(_norm(c, proj(p.wk), p.k_norm)), heads(proj(p.wv))


def _rows_of(a: Array, l: Array, rows: Array) -> Array:
    """a[l, rows]: (B, ...) of a state array (layers, rows, ...), a slice a row
    (no gather touches the array: it keeps one layout from parameter to result)."""
    tail = a.shape[2:]
    zeros = (0,) * len(tail)
    return jnp.concatenate([jax.lax.dynamic_slice(a, (l, rows[b], *zeros), (1, 1, *tail))[0] for b in range(rows.shape[0])])


def _put_rows(a: Array, l: Array, rows: Array, new: Array) -> Array:
    """a with a[l, rows[b]] = new[b], an in-place slice write a row, in order."""
    zeros = (0,) * (a.ndim - 2)
    for b in range(rows.shape[0]):
        a = jax.lax.dynamic_update_slice(a, new[b][None, None].astype(a.dtype), (l, rows[b], *zeros))
    return a


class OlmoHybrid:
    """Namespace of pure functions over (OlmoHybridConfig, OlmoHybridParams)."""

    weight_decay_mask = None
    route_stats = None
    verify_step_paged = None  # no speculative verify: a rejected draft would need the state it started from
    prefill_batched = True  # the chunks of a round's slots ride one call; each row's state by its row index
    prefill_rows = staticmethod(GPT.prefill_rows)  # every weight is dense and sees every row
    kernel_sweep_whole = True  # every full layer's decode attention is this one kernel call

    @staticmethod
    def init(config: OlmoHybridConfig, key: KeyArray) -> OlmoHybridParams:
        c = config
        D, F, H, dk, dv, E = c.n_embd, c.dense_width, c.linear_heads, c.linear_key_dim, c.linear_value_dim, c.n_embd
        ones = jnp.ones((D,))

        def init_linear(k: KeyArray) -> LinearLayerParams:
            ks = jax.random.split(k, 13)
            dt = jnp.exp(jax.random.uniform(ks[8], (H,), minval=math.log(1e-3), maxval=math.log(1e-1)))
            return LinearLayerParams(
                wq=_linear(ks[0], H * dk, D), wk=_linear(ks[1], H * dk, D), wv=_linear(ks[2], H * dv, D),
                conv=jax.random.normal(ks[3], (c.conv_channels, c.conv_kernel)) / math.sqrt(c.conv_kernel),
                w_beta=_linear(ks[4], H, D), w_a=W_A_INIT * _linear(ks[5], H, D),
                a_log=jnp.log(jax.random.uniform(ks[7], (H,), minval=1.0, maxval=16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                wg=_linear(ks[6], H * dv, D), o_norm=jnp.ones((dv,)), wo=_linear(ks[9], D, H * dv), norm_attn=ones,
                w_gate=_linear(ks[10], F, D), w_up=_linear(ks[11], F, D), w_down=_linear(ks[12], D, F), norm_mlp=ones,
            )

        def init_full(k: KeyArray) -> FullLayerParams:
            ks = jax.random.split(k, 7)
            return FullLayerParams(
                wq=_linear(ks[0], E, D), wk=_linear(ks[1], E, D), wv=_linear(ks[2], E, D), q_norm=jnp.ones((E,)),
                k_norm=jnp.ones((E,)), wo=_linear(ks[3], D, E), norm_attn=ones,
                w_gate=_linear(ks[4], F, D), w_up=_linear(ks[5], F, D), w_down=_linear(ks[6], D, F), norm_mlp=ones,
            )

        k_embed, k_head, k_lin, k_full = jax.random.split(key, 4)
        return OlmoHybridParams(
            wte=jax.random.normal(k_embed, (c.vocab_size, D)),  # unit rows: layer 0's mixer reads the stream un-normed
            linear=jax.vmap(init_linear)(jax.random.split(k_lin, c.n_linear)),
            full=jax.vmap(init_full)(jax.random.split(k_full, c.n_periods)),
            final_norm=ones, lm_head=jax.random.normal(k_head, (c.vocab_size, D)) / math.sqrt(D),
        )

    @staticmethod
    def cast_params(params: OlmoHybridParams, dtype) -> OlmoHybridParams:
        """The compute copy: matrices and taps in `dtype`; `A_log`, `dt_bias` and every norm gain as they are."""
        return jax.tree_util.tree_map_with_path(
            lambda path, p: p if str(getattr(path[-1], "name", path[-1])) in _F32_LEAVES
            or not jnp.issubdtype(p.dtype, jnp.floating) else p.astype(dtype),
            params,
        )

    @staticmethod
    def count_params(params: OlmoHybridParams) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    @staticmethod
    def param_specs(config, tree, mesh):
        del config, mesh  # every leaf replicated: no mesh axis is wired (check_experiment)
        return jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree)

    @staticmethod
    def flops_per_token(config: OlmoHybridConfig, seq_len: tp.Optional[int] = None, stats=None) -> float:
        """FORWARD FLOPs a token (this family is served, not trained): 2 x the
        parameters a token multiplies, the delta rule's three (d_k, d_v)
        products a head of a linear layer, and a full layer's scores and values
        over a causal context."""
        del stats
        c = config
        T = seq_len or c.block_size
        D, F, H, dk, dv, E = c.n_embd, c.dense_width, c.linear_heads, c.linear_key_dim, c.linear_value_dim, c.n_embd
        linear = D * H * (2 * dk + 2 * dv) + 2 * D * H + H * dv * D + c.conv_channels * c.conv_kernel + 3 * H * dk * dv
        full = 4 * D * E + 2 * E * T / 2
        return 2.0 * (c.n_linear * linear + c.n_periods * full + c.n_layer * 3 * D * F + c.vocab_size * D)

    # ------------------------------------------------------------------
    # the stack: what every forward shares
    # ------------------------------------------------------------------

    @staticmethod
    def _run(c: OlmoHybridConfig, params: OlmoHybridParams, x: Array, carry, linear_mix, full_mix):
        """Every period applied to x (..., D) float32. `linear_mix(carry, l, p,
        a) -> (out, carry)` is the caller's mixer for linear layer `l` (a traced
        index into the state arrays) with parameters `p` over the stream `a` in
        the matrices' dtype; `full_mix(carry, row, p, a)` the same for the full
        layer of cache layer `row`. `carry` (pools, states, or None) rides the
        loop. Returns (x, carry)."""
        m = c.period - 1

        def period(i, state):
            x, carry = state
            for j in range(m):
                p = _Layer(params.linear, i * m + j)
                with jax.named_scope("attn_linear"):
                    o, carry = linear_mix(carry, i * m + j, p, x.astype(p.dtype))
                    x = x + _norm(c, o, p.norm_attn, x.dtype)
                x = _mlp(c, p, x)
            p = _Layer(params.full, i)
            with jax.named_scope("attn_global"):
                o, carry = full_mix(carry, i, p, x.astype(p.dtype))
                x = x + _norm(c, o, p.norm_attn, x.dtype)
            return _mlp(c, p, x), carry

        return jax.lax.fori_loop(0, c.n_periods, period, (x, carry))

    @staticmethod
    def _embed(params: OlmoHybridParams, tokens: Array) -> Array:
        with jax.named_scope("embed"):
            return jnp.take(params.wte, tokens, axis=0).astype(jnp.float32)

    @staticmethod
    def _head(c: OlmoHybridConfig, params: OlmoHybridParams, x: Array) -> Array:
        with jax.named_scope("lm_head"):
            return jnp.einsum("...d,vd->...v", _norm(c, x, params.final_norm, params.lm_head.dtype), params.lm_head)

    @staticmethod
    def _conv(p: LinearLayerParams, window: Array, T: int) -> Array:
        """silu of the causal convolution over `window` (B, K - 1 + T, channels): the K - 1 inputs before the T tokens, then them."""
        K = p.conv.shape[-1]
        taps = p.conv.astype(jnp.float32)
        y = sum(window[:, j : j + T].astype(jnp.float32) * taps[:, j] for j in range(K))
        return jax.nn.silu(y).astype(window.dtype)

    # ------------------------------------------------------------------
    # the plain full forward (tests, sample.py's scoring; no cache)
    # ------------------------------------------------------------------

    @staticmethod
    def hidden(config: OlmoHybridConfig, params: OlmoHybridParams, tokens: Array, *, key=None,
               inference: bool = False, attn_fn=None) -> Array:
        """Whole sequences (B, T) -> the stream before the final norm (B, T, D)."""
        del key, inference
        if attn_fn is not None:
            raise ValueError(f"{FAMILY}: a mesh-bound attn_fn is not wired")
        c = config
        B, T = tokens.shape
        pos = jnp.arange(T)
        keep = pos[None, :] <= pos[:, None]

        def linear_mix(carry, l, p, a):
            u = _project(p, a)
            window = jnp.pad(u, ((0, 0), (c.conv_kernel - 1, 0), (0, 0)))
            q, k, v = _split_qkv(c, OlmoHybrid._conv(p, window, T))
            g, beta = _gates(c, p, a)
            with jax.named_scope("linear_state"):
                o, _ = kda_chunked(q, k, v, g, beta)
            return _gated_out(c, p, a, o.astype(jnp.float32)), carry

        def full_mix(carry, row, p, a):
            q, k, v = _qkv_full(c, p, a)
            s = jnp.einsum("bthc,bshc->bhts", q, k).astype(jnp.float32) / math.sqrt(c.head_dim)
            prob = jax.nn.softmax(jnp.where(keep, s, float("-inf")), axis=-1).astype(v.dtype)
            o = jnp.einsum("bhts,bshc->bthc", prob, v).reshape(B, T, -1)
            return jnp.einsum("bte,de->btd", o, p.wo), carry

        return OlmoHybrid._run(c, params, OlmoHybrid._embed(params, tokens), None, linear_mix, full_mix)[0]

    @staticmethod
    def apply(config: OlmoHybridConfig, params: OlmoHybridParams, tokens: Array) -> Array:
        """Logits (B, T, V) of whole sequences."""
        return OlmoHybrid._head(config, params, OlmoHybrid.hidden(config, params, tokens))

    # ------------------------------------------------------------------
    # serving (sampling/serve.py reaches these through models/__init__.py)
    # ------------------------------------------------------------------

    @staticmethod
    def cache_kinds(config: OlmoHybridConfig):
        """One PAGED kind (the full layers keep the whole context) and one
        STATE kind: a row a slot, of the shapes `state_shapes` gives."""
        return (CacheKind(GLOBAL, 0, 0), StateKind(STATE, config.state_shapes))

    @staticmethod
    def init_cache(config: OlmoHybridConfig, num_pages: tp.Sequence[int], page_size: int = 8,
                   dtype=jnp.bfloat16, kernel_layout: bool = False) -> ServeCache:
        """Zeroed K and V pools of `num_pages[0]` pages and one cache layer a
        full layer, and the state kind's arrays at `num_pages[1]` rows: the
        delta-rule states (n_linear, rows, H, d_v, d_k) float32 and the
        convolution's history (n_linear, rows, (conv_kernel - 1) * channels).
        Counter: `gdn_counts` (3,) int32, the decoded tokens, prefilled tokens
        and prefill chunks the linear layers have taken."""
        c = config
        return ServeCache.zeros(FAMILY, (((c.n_periods, c.n_head, c.head_dim),) * 2,), num_pages, page_size, dtype, kernel_layout,
                                (jnp.zeros((3,), jnp.int32),), state_kind=OlmoHybrid.cache_kinds(c)[1])

    @staticmethod
    def kernel_sweep(config: OlmoHybridConfig, cache: ServeCache):
        """(pool shape, q rows a pool head, window, sinks) of the decode kernel's sweep."""
        return cache.pools[0][0].shape, 1, 0, 0

    @staticmethod
    def serve_counters(config: OlmoHybridConfig, cache: ServeCache) -> tp.Dict[str, float]:
        """`gdn.decode_tokens` (one-token updates of an active slot, a layer
        counted once), `gdn.prefill_tokens` / `gdn.prefill_chunks` (tokens and
        slot-chunks the chunk-carrying scan has taken), and what the K/V pools
        keep of a token over the full layers, in bytes."""
        n = [int(x) for x in jax.device_get(cache.counters[0])]
        return {"gdn.decode_tokens": n[0], "gdn.prefill_tokens": n[1], "gdn.prefill_chunks": n[2],
                f"kv.{GLOBAL}_bytes_per_token": sum(a.nbytes for a in cache.pool_arrays()) / (cache.num_pages * cache.page_size)}

    @staticmethod
    def decode_step_paged(config: OlmoHybridConfig, params: OlmoHybridParams, token: Array, cache: ServeCache,
                          page_table, lengths: Array, active: Array,
                          attn_impl: str = "auto", mesh=None, split_k: int = 1) -> tp.Tuple[Array, ServeCache]:
        """One decode step for the B slots at B positions (GPT.decode_step_paged's
        contract). `page_table` is (the global kind's (B, pages), the state rows
        (B,)); the batch IS the slots in order and a slot's row is the row of
        its index (`PagePool.tables` builds a decode round's rows and refuses
        any other), so the update runs over rows [0, B) where they lie and the
        row vector is not read: no gather, no scatter. An ACTIVE slot's state
        and convolution history advance by its token; an inactive slot's stay
        bit for bit (it may be in the middle of its chunked prefill), and it
        writes no key. Returns (logits (B, V), cache)."""
        from midgpt_tpu.kernels.decode_attention import paged_attention, resolve_paged_impl

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

        def linear_mix(carry, l, p, a):  # a (B, D)
            pools, (S, hist) = carry
            u = _project(p, a)
            h0 = jax.lax.dynamic_index_in_dim(hist, l, 0, keepdims=False)[:B].reshape(B, -1, u.shape[-1])  # (B, K - 1, channels)
            window = jnp.concatenate([h0, u[:, None].astype(h0.dtype)], axis=1)
            q, k, v = _split_qkv(c, OlmoHybrid._conv(p, window, 1)[:, 0])
            g, beta = _gates(c, p, a)
            with jax.named_scope("linear_state"):
                S0 = jax.lax.dynamic_index_in_dim(S, l, 0, keepdims=False)[:B]
                o, S1 = kda_step(q, k, v, g, beta, S0)
                S = jax.lax.dynamic_update_slice(S, jnp.where(active[:, None, None, None], S1, S0)[None], (l, 0, 0, 0, 0))
            h1 = jnp.where(active[:, None, None], window[:, 1:], h0).reshape(B, -1)
            hist = jax.lax.dynamic_update_slice(hist, h1[None], (l, 0, 0))
            return _gated_out(c, p, a, o), (pools, (S, hist))

        def full_mix(carry, row, p, a):
            pools, state = carry
            q, k, v = _qkv_full(c, p, a)
            ck, cv, _, _ = _paged_write((*pools, None, None), row, write_pages, offs, k, v, attn_impl, None)
            o = paged_attention(q, ck, cv, table, counts, impl=attn_impl, split_k=split_k, layer=row)
            return jnp.einsum("be,de->bd", o.astype(p.dtype).reshape(B, -1), p.wo), ((ck, cv), state)

        x, ((ck, cv), state) = OlmoHybrid._run(
            c, params, OlmoHybrid._embed(params, token), (cache.pools[0], cache.state), linear_mix, full_mix)
        counted = cache.counters[0].at[0].add(jnp.sum(active.astype(jnp.int32)))
        return OlmoHybrid._head(c, params, x), ServeCache(pools=((ck, cv),), state=state, counters=(counted,))

    @staticmethod
    def prefill_paged_chunk(config: OlmoHybridConfig, params: OlmoHybridParams, tokens: Array, start: Array,
                            n_valid: Array, cache: ServeCache, page_table,
                            attn_impl: str = "auto", mesh=None) -> tp.Tuple[Array, ServeCache]:
        """The prompt chunks of B requests, row b's being [start[b], start[b] +
        n_valid[b]) (GPT.prefill_paged_chunk's contract). `page_table` is (the
        global kind's (B, pages), the state rows (B,)): row b's delta-rule
        state and convolution history are READ from state row `rows[b]` (what
        its chunk before left) unless its chunk is the prompt's first (start
        0), which begins from ZEROS whatever the row holds: that is the row's
        reset, so a request admitted to a slot never sees what the slot's last
        request left (models/__init__.py, "a state kind"); the state after its
        n_valid tokens is WRITTEN back; tokens past n_valid change neither (g =
        0, beta = 0: no decay, no write; the history is cut at n_valid). An
        empty place (n_valid 0) names the sink row. The full layers write their
        K/V, then each row attends through the multi-row paged attention under
        its own counts. Returns (logits of each
        row's last valid position (B, V), cache); the ONE-ROW call (scalar
        `start` / `n_valid`) returns (1, 1, V)."""
        from midgpt_tpu.kernels.decode_attention import paged_verify_attention, resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        table, rows = page_table
        one_row = jnp.ndim(start) == 0
        start, n_valid, rows = jnp.reshape(start, (-1,)), jnp.reshape(n_valid, (-1,)), jnp.reshape(rows, (-1,))
        B, T = tokens.shape
        K1 = c.conv_kernel - 1
        ps = cache.page_size
        t_idx = jnp.arange(T, dtype=jnp.int32)
        positions = start[:, None] + t_idx
        valid = t_idx < n_valid[:, None]  # (B, T)
        write_pages = jnp.where(valid, jnp.take_along_axis(table, positions // ps, axis=1), cache.num_pages)
        offs = positions % ps
        # row t sees start + t + 1 keys; pad rows what the last valid row sees, an empty row one key
        counts = jnp.maximum(jnp.minimum(positions, (start + n_valid)[:, None] - 1) + 1, 1)

        def carried(a, l):  # row b's slice of a state array, zeros where its prompt starts here
            a = _rows_of(a, l, rows)
            return jnp.where(jnp.reshape(start == 0, (B,) + (1,) * (a.ndim - 1)), jnp.zeros((), a.dtype), a)

        def linear_mix(carry, l, p, a):  # a (B, T, D)
            pools, (S, hist) = carry
            u = _project(p, a)
            window = jnp.concatenate([carried(hist, l).reshape(B, K1, -1), u.astype(hist.dtype)], axis=1)  # (B, K - 1 + T, channels)
            q, k, v = _split_qkv(c, OlmoHybrid._conv(p, window, T))
            g, beta = _gates(c, p, a)
            with jax.named_scope("linear_state"):
                o, S1 = kda_chunked(q, k, v, jnp.where(valid[..., None], g, 0.0), jnp.where(valid[..., None], beta, 0.0),
                                    carried(S, l))
                S = _put_rows(S, l, rows, S1)
            # the inputs at [n_valid - (K - 1), n_valid): the window's rows n_valid .. n_valid + K - 2
            keep = jnp.take_along_axis(window, (n_valid[:, None] + jnp.arange(K1))[:, :, None], axis=1)
            return _gated_out(c, p, a, o.astype(jnp.float32)), (pools, (S, _put_rows(hist, l, rows, keep.reshape(B, -1))))

        def full_mix(carry, row, p, a):
            pools, state = carry
            q, k, v = _qkv_full(c, p, a)
            ck, cv, _, _ = _paged_write((*pools, None, None), row, write_pages, offs, k, v, attn_impl, None)
            # the multi-row paged attention, PREFILL_ATTN_ROWS rows of every slot's chunk a call; the innermost scope
            # names the custom calls `prefill_attn.<n>` in the device trace (as GPT.prefill_paged_chunk does), apart
            # from `attn_global`'s decode kernel
            with jax.named_scope("prefill_attn"):
                o = jnp.concatenate([
                    paged_verify_attention(q[:, t : t + PREFILL_ATTN_ROWS], ck, cv, table, counts[:, t : t + PREFILL_ATTN_ROWS],
                                           impl=attn_impl, layer=row)
                    for t in range(0, T, PREFILL_ATTN_ROWS)], axis=1)  # (B, T, H, C)
            return jnp.einsum("bte,de->btd", o.astype(p.dtype).reshape(B, T, -1), p.wo), ((ck, cv), state)

        x, ((ck, cv), state) = OlmoHybrid._run(
            c, params, OlmoHybrid._embed(params, tokens), (cache.pools[0], cache.state), linear_mix, full_mix)
        last = jnp.take_along_axis(x, jnp.maximum(n_valid - 1, 0)[:, None, None], axis=1)  # (B, 1, D)
        logits = OlmoHybrid._head(c, params, last)
        counted = cache.counters[0] + jnp.stack([jnp.zeros((), jnp.int32), jnp.sum(n_valid), jnp.sum((n_valid > 0).astype(jnp.int32))])
        return (logits if one_row else logits[:, 0]), ServeCache(pools=((ck, cv),), state=state, counters=(counted,))
