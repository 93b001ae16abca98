"""Plain float32 reference of Kimi-Linear (KDA + NoPE-MLA + sigmoid-routed MoE),
as one chip's share of an expert-parallel job holds it.

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`,
no kernel, no chunking of the recurrence, no dispatch: the recurrence runs
token by token, attention by blocks of queries against all the keys (so that
T = 8,192 fits), the experts by a loop over the experts held with a mask. It
imports nothing of `midgpt_tpu`: it reads the parameter arrays BY NAME off
whatever object holds them (`params.layers[i].mixer.w_qkv`, ...), and the
sizes from a plain dict (`dataclasses.asdict` of the model config).

Source of the equations: the published config
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json)
and the family's open implementation (flash-linear-attention's KDA layer). With
h a layer's input (T, D), RMSNorm_w with a weight and eps `rms_norm_eps`:

    x = x + Mixer(RMSNorm_w(x));  x = x + MLP(RMSNorm_w(x));  final RMSNorm_w;  untied lm_head

KDA (layer numbers in `kda_layers`, 1-based): q, k, v = SiLU(conv(W_q h)), ...
with conv a causal depthwise convolution of `kda_conv_size` taps; q, k
L2-normalised per head, q times d_k^-1/2; g_t = -exp(A_log[head]) *
softplus(W_fb(W_fa h_t) + dt_bias) per channel; beta_t = sigmoid(W_b h_t);
per head, S (d_k, d_v):

    S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t

out = W_o(RMSNorm_w(o_t) * sigmoid(W_gb(W_ga h_t))), the norm per head.

MLA (layers in `full_attn_layers`): q = W_q h as (H, nope + rope); [c, k_pe] =
W_kva h; [k_nope, v] = W_kvb RMSNorm_w(c); k = [k_nope, k_pe]; causal
softmax(q k^T / sqrt(nope + rope)) v; W_o.

MLP: SwiGLU W_down(SiLU(W_gate h) * W_up h); dense in the first
`first_k_dense` layers; after them s = sigmoid(W_r h) over `n_experts`, the
`moe_top_k` largest of s + router_bias selected, weights the selected s
(without the bias) over their sum, times `routed_scaling_factor`; output
sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h).

Departures from the published model, each on purpose:
  * ONE CHIP'S SHARE. Only experts [expert_offset, expert_offset + n_held)
    are held (n_held = the expert weights' leading axis). The router scores
    all `n_experts`, top-k and the renormalisation are over all of them, and
    what the absent experts would add is LEFT OUT; that partial result goes on
    to the next layer. The embedding and the head have `vocab_size` rows (a
    slice of the published 163,840): ids, logits and the loss are over it.
    `moe_layer(..., include_shared=False)` over all the shares plus the shared
    expert once is the uncut layer (tests/test_kimi_linear.py).
  * NoPE: `mla_use_nope` is true in the published config, so NO rotation is
    applied to the `qk_rope_head_dim` channels of q or k_pe; k_pe is an extra
    64 channels of key shared by all heads.
  * Sizes the catalog's config does not give, by the family's convention (the
    configuration file lists them under `assumed`): the rank of the decay and
    gate pairs (= kda_head_dim), no bias on any projection, `dt_bias` on the
    decay only, L2-norm eps 1e-6, `num_expert_group` = `topk_group` = 1 is
    plain top-k.
  * The router's correction bias is a small seeded value and no balancing
    rule moves it (the program runs none).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(w)


def _swiglu(h, p):
    return (jax.nn.silu(h @ _f32(p.w_gate).T) * (h @ _f32(p.w_up).T)) @ _f32(p.w_down).T


def _conv(x, taps):
    """x (T, C), taps (C, K): y_t = sum_j taps[:, j] x_{t-(K-1)+j}."""
    K, T = taps.shape[1], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(xp[j : j + T] * taps[:, j] for j in range(K))


def kda_layer(p, h, cfg):
    T = h.shape[0]
    H, d = cfg["n_head"], cfg["kda_head_dim"]
    q, k, v = (
        jax.nn.silu(_conv(h @ _f32(p.w_qkv[i]).T, _f32(p.conv[i]))).reshape(T, H, d) for i in range(3)
    )
    l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k = l2(q) * d**-0.5, l2(k)
    f = (h @ _f32(p.w_fa).T) @ _f32(p.w_fb).T + _f32(p.dt_bias)
    g = -jnp.exp(_f32(p.A_log))[:, None] * jax.nn.softplus(f).reshape(T, H, d)
    beta = jax.nn.sigmoid(h @ _f32(p.w_b).T)  # (T, H)

    def step(S, x):  # S (H, d_k, d_v)
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, :, None] * S
        S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32), (q, k, v, g, beta))
    gate = jax.nn.sigmoid((h @ _f32(p.w_ga).T) @ _f32(p.w_gb).T).reshape(T, H, d)
    return (_rms(o, p.o_norm, cfg["rms_norm_eps"]) * gate).reshape(T, H * d) @ _f32(p.wo).T


def mla_layer(p, h, cfg):
    T = h.shape[0]
    H, dn, dr, dv, r = (cfg["n_head"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"], cfg["kv_lora_rank"])
    q = (h @ _f32(p.wq).T).reshape(T, H, dn + dr)
    ckv = h @ _f32(p.w_kva).T
    kv = (_rms(ckv[:, :r], p.kv_norm, cfg["rms_norm_eps"]) @ _f32(p.w_kvb).T).reshape(T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(ckv[:, None, r:], (T, H, dr))], axis=-1)  # no rotation
    v = kv[..., dn:]
    bq = min(QUERY_BLOCK, T)
    pad = -T % bq
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, bq, H, dn + dr)
    pos = jnp.arange(T + pad).reshape(-1, bq)

    def block(x):
        q_i, pos_i = x
        s = jnp.einsum("qhc,khc->hqk", q_i, k) / math.sqrt(dn + dr)
        s = jnp.where(pos_i[None, :, None] >= jnp.arange(T)[None, None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khc->qhc", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (qb, pos)).reshape(T + pad, H * dv)[:T]
    return o @ _f32(p.wo).T


def moe_layer(p, h, cfg, include_shared=True):
    """The share of the layer that the experts in `p` give (+ the shared expert)."""
    s = jax.nn.sigmoid(h @ _f32(p.router).T)  # (T, n_experts)
    _, sel = jax.lax.top_k(s + _f32(p.router_bias), cfg["moe_top_k"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    y = _swiglu(h, p.shared) if include_shared else jnp.zeros_like(h)
    for e in range(p.w_gate.shape[0]):  # the experts held, each over every token, masked
        w_e = jnp.sum(jnp.where(sel == cfg["expert_offset"] + e, w, 0.0), axis=-1)
        up = jax.nn.silu(h @ _f32(p.w_gate[e]).T) * (h @ _f32(p.w_up[e]).T)
        y = y + (up @ _f32(p.w_down[e]).T) * w_e[:, None]
    return y


def hidden_one(params, tokens, cfg):
    """(T,) ids -> final-normed hidden states (T, D)."""
    eps = cfg["rms_norm_eps"]
    x = _f32(params.wte)[tokens]
    for i, layer in enumerate(params.layers):
        n = i + 1
        if n in cfg["kda_layers"]:
            mixer = kda_layer
        elif n in cfg["full_attn_layers"]:
            mixer = mla_layer
        else:
            raise ValueError(f"layer {n} is in neither kda_layers nor full_attn_layers")
        x = x + mixer(layer.mixer, _rms(x, layer.norm1, eps), cfg)
        h = _rms(x, layer.norm2, eps)
        x = x + (_swiglu(h, layer.mlp) if i < cfg["first_k_dense"] else moe_layer(layer.mlp, h, cfg))
    return _rms(x, params.final_norm, eps)


def logits(params, tokens, cfg):
    """(B, T) ids -> (B, T, V) float32 logits over the vocabulary rows held."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda t: hidden_one(params, t, cfg) @ _f32(params.lm_head).T, tokens)


def token_losses(params, x, y, cfg):
    """(B, T) float32 per-token cross-entropy of `logits` against y."""
    with jax.default_matmul_precision("highest"):
        lg = logits(params, x, cfg)
        picked = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(lg, axis=-1) - picked
