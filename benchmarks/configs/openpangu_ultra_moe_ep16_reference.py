"""Plain float32 reference of openPangu-Ultra-MoE (latent attention with a
low-rank query and one rotated key group shared by the heads, sandwich norms,
sigmoid-routed experts beside a shared expert), as one chip's share of an
expert-parallel deployment holds it.

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`:
whole sequences, no cache, no pages, no kernel, no batching, and NO ABSORPTION:
attention is the published, EXPANDED form, K and V of every head made from the
latent (`W_kvb c`) and scored as 192-channel keys, so the program's absorbed,
cached decode is checked against the mathematics it rearranges. Explicit masks
(QUERY_BLOCK rows of the (T, T) mask at a time, each against every key, so
that a few thousand tokens' scores of 128 heads fit beside the weights); the
experts by a loop over the experts held. It imports nothing of `midgpt_tpu`: it
reads the parameter arrays BY NAME off whatever object holds them
(`params.layers[i].attn.w_qa`, ...) and the sizes from a plain dict
(`dataclasses.asdict` of the model config). It runs HALF a layer at a time (one
jitted call for the attention, one for the MLP, the matrices cast to float32
inside), so that at the published widths at most one half's float32 weights
(16 held experts and the shared one: 3.2 GB) are live beside the served copy.

Source of the equations: the published config
(https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json).
With h a sublayer's input (T, D) and n(.) an RMSNorm carrying a weight, eps
`rms_norm_eps`:

    x = E[t];  x = x + n_post_attn(MLA(n_in(x)));  x = x + n_post_mlp(F(n_pre_mlp(x)));  final n;  untied head

(`sandwich_norm`: the sublayer's OUTPUT is normed before it is added.)
MLA: c_q = n_q(W_qa h); q = W_qb c_q as (n_head, nope + rope); [c_kv; k_r] =
W_kva h; c = n_kv(c_kv); [k_n; v] = W_kvb c as (n_head, nope + v); rotate-half
rotary on q's last `rope` channels and on the ONE k_r every head shares, angle
pos * `rope_theta`^(-2i / rope) for the channel pair (i, i + rope / 2); a = (q_n
. k_n + q_r . k_r) / sqrt(nope + rope); key j visible to query i iff j <= i;
softmax; values v; W_o.
F: layer i < `first_k_dense`: SwiGLU W_down(SiLU(W_gate h) * W_up h). Else
shared(h) + sum_k w_k expert_k(h): s = sigmoid(W_r h) over `n_experts`, the
`moe_top_k` largest selected (no groups, no correction bias), weights the
selected s over their sum (`moe_renormalize`) times `routed_scaling_factor`.

Departures from the published model, each on purpose:
  * ONE CHIP'S SHARE. Only experts [expert_offset, expert_offset + n_held) are
    held (n_held = the expert weights' leading axis); the router scores all
    `n_experts`, top-k and the renormalisation are over all of them, and what
    the absent experts would add is LEFT OUT; the shared expert is whole;
    that partial result goes on to the next layer. Embedding and head have
    `vocab_size` rows (a slice of the published 153,600). The routed parts of
    every share plus the shared expert ONCE are the uncut layer
    (tests/test_pangu_ultra.py).
  * The next-token-prediction layer (`num_nextn_predict_layers` 1) is left
    out: the published forward without speculation does not run it.
  * Readings of the config that are the writer's (the configuration file lists
    them under `assumed`): the sigmoid scoring with renormalisation and the 2.5
    factor, the placement of the two extra norms, rotate-half rotary.

`round_to` (a dtype) rounds every matrix but the router to that dtype before
the float32 cast: the cell's 8-bit reading (`float8_e4m3fn`), which its limits
must refuse.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256  # query rows whose (heads, rows, T) scores are live at once


def _f32(a, round_to=None):
    if round_to is not None and a.ndim >= 2:
        # behind a barrier: the compiler may drop a narrowing convert that is
        # widened again at once (xla_allow_excess_precision), and on the chip did
        a = jax.lax.optimization_barrier(a.astype(round_to))
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _swiglu(h, p, f):
    return (jax.nn.silu(h @ f(p.w_gate).T) * (h @ f(p.w_up).T)) @ f(p.w_down).T


def _rotate(x, base):
    """x (T, heads, rot): rotate-half over all `rot` channels, position = row."""
    T, rot = x.shape[0], x.shape[-1]
    inv = base ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)  # (rot / 2,)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : rot // 2], x[..., rot // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mla_layer(p, h, cfg, f=_f32):
    """h (T, D) -> (T, D): latent attention in its expanded form."""
    T = h.shape[0]
    H, dn, dr, dv, r = (cfg["n_head"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"], cfg["kv_lora_rank"])
    eps, base = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = (_rms(h @ f(p.w_qa).T, p.q_norm, eps) @ f(p.w_qb).T).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], base)], axis=-1)
    ckv = h @ f(p.w_kva).T  # (T, r + dr)
    kv = (_rms(ckv[:, :r], p.kv_norm, eps) @ f(p.w_kvb).T).reshape(T, H, dn + dv)
    k_r = _rotate(ckv[:, None, r:], base)  # (T, 1, dr): one rotated key group, shared by the heads
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (T, H, dr))], axis=-1)
    v = kv[..., dn:]
    j = jnp.arange(T)[None, :]
    out = []
    for r0 in range(0, T, QUERY_BLOCK):  # rows [r0, r0 + QUERY_BLOCK) against every key
        n = min(QUERY_BLOCK, T - r0)
        a = jnp.einsum("ihc,jhc->hij", q[r0:r0 + n], k) / math.sqrt(dn + dr)
        a = jnp.where((j <= r0 + jnp.arange(n)[:, None])[None], a, -jnp.inf)
        out.append(jnp.einsum("hij,jhc->ihc", jax.nn.softmax(a, axis=-1), v).reshape(n, H * dv))
    return jnp.concatenate(out) @ f(p.wo).T


def moe_layer(p, h, cfg, f=_f32, include_shared=True):
    """h (T, D) -> the part of the expert layer's output that the experts held
    (`p.w_gate`'s leading axis, from `expert_offset`) give, plus the shared expert."""
    s = jax.nn.sigmoid(h @ p.router.astype(jnp.float32).T)  # (T, n_experts): the router is never rounded
    _, idx = jax.lax.top_k(s, cfg["moe_top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    y = _swiglu(h, p.shared, f) if include_shared else jnp.zeros_like(h)

    def one(e, y):  # the experts held here, each over every token, masked by its pair weight
        w_e = jnp.sum(jnp.where(idx == cfg["expert_offset"] + e, w, 0.0), axis=-1)  # (T,), 0 where e was not selected
        up = jax.nn.silu(h @ f(p.w_gate[e]).T) * (h @ f(p.w_up[e]).T)
        return y + w_e[:, None] * (up @ f(p.w_down[e]).T)

    return jax.lax.fori_loop(0, p.w_gate.shape[0], one, y)


def _post(x, w, cfg):
    return x if w is None else _rms(x, w, cfg["rms_norm_eps"])


def attention_half(p, x, cfg, f=_f32):
    return x + _post(mla_layer(p.attn, _rms(x, p.norm_in, cfg["rms_norm_eps"]), cfg, f), p.norm_post_attn, cfg)


def mlp_half(p, x, cfg, i, f=_f32):
    h = _rms(x, p.norm_pre_mlp, cfg["rms_norm_eps"])
    y = _swiglu(h, p.mlp, f) if i < cfg["first_k_dense"] else moe_layer(p.mlp, h, cfg, f)
    return x + _post(y, p.norm_post_mlp, cfg)


def layer(p, x, cfg, i, f=_f32):
    return mlp_half(p, attention_half(p, x, cfg, f), cfg, i, f)


def logits(params, tokens, cfg, last=None, rows=None, round_to=None):
    """tokens (T,) int -> float32 logits (T, V), or of the `last` positions
    only, or of the positions `rows` (an int array) only. Two jitted calls a
    layer."""
    f = lambda a: _f32(a, round_to)
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: jnp.take(f(e), t, axis=0))(params.wte, tokens)
        for i, p in enumerate(params.layers):
            x = jax.jit(lambda p, x: attention_half(p, x, cfg, f))(p, x)
            x = jax.jit(lambda p, x, i=i: mlp_half(p, x, cfg, i, f))(p, x)
        if last is not None:
            x = x[-last:]
        if rows is not None:
            x = jnp.take(x, jnp.asarray(rows), axis=0)
        head = lambda w, hw, x: _rms(x, w, cfg["rms_norm_eps"]) @ f(hw).T
        return jax.jit(head)(params.final_norm, params.lm_head, x)
