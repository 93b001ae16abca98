"""Plain float32 reference of MiMo-V2 (window + global attention, sink bias,
192 / 128 heads, partial rotary, sigmoid-routed experts), as one chip's share of
an expert-parallel deployment holds it.

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`:
whole sequences, no cache, no pages, no kernel, no batching; explicit masks
(QUERY_BLOCK rows of the (T, T) mask at a time, each against every key, so
that a few thousand tokens' scores fit beside the weights); the sink bias as an extra COLUMN concatenated to the scores before an
ordinary softmax and dropped after it; the experts by a loop over the experts
held. It imports nothing of `midgpt_tpu`: it reads the parameter arrays BY NAME
off whatever object holds them (`params.layers[i].attn.wq`, ...) and the sizes
from a plain dict (`dataclasses.asdict` of the model config). It runs a layer
at a time (one jitted call a layer, the layer's matrices cast to float32
inside), so that at the published widths one layer's float32 weights (2.0 GB)
are live at once.

Source of the equations: the published config
(https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json). With h a
layer's input (T, D) and RMSNorm_w carrying a weight, eps `rms_norm_eps`:

    x = E[t];  x = x + Attn_i(RMSNorm_w(x));  x = x + FFN_i(RMSNorm_w(x));  final RMSNorm_w;  untied head

Attention: q = W_q h as (n_head, dq); k = W_k h as (n_kv, dq); v =
`attention_value_scale` * W_v h as (n_kv, dv); rotate-half rotary on the first
rot = int(dq * `partial_rotary_factor`) channels of every q and k head, angle
pos * base^(-2i / rot) for the channel pair (i, i + rot / 2); q head h reads kv
head h // (n_head / n_kv); a = q.k / sqrt(dq).
  layer_pattern[i] == 0 (global): n_kv = `n_kv_heads`, dq/dv = `head_dim` /
    `v_head_dim`, base `rope_theta`, key j visible to query i iff j <= i, plain
    softmax (`full_sink_bias` false).
  layer_pattern[i] == 1 (window): `swa_n_kv_heads`, `swa_head_dim` /
    `swa_v_head_dim`, base `swa_rope_theta`, visible iff i - `sliding_window` <
    j <= i, and softmax over [a_i., s_h] with the sink column dropped:
    p_ij = exp(a_ij) / (exp(s_h) + sum_j' exp(a_ij')) (`swa_sink_bias` true).
FFN: moe_layer_freq[i] == 0: SwiGLU W_down(SiLU(W_gate h) * W_up h). Else s =
sigmoid(W_r h) over `n_experts`, the `moe_top_k` largest of s + router_bias
selected, weights the selected s (without the bias) over their sum
(`moe_renormalize`), times `routed_scaling_factor`; y = sum_e w_e SwiGLU_e(h).
No shared expert.

Departures from the published model, each on purpose:
  * ONE CHIP'S SHARE. Only experts [expert_offset, expert_offset + n_held) are
    held (n_held = the expert weights' leading axis); the router scores all
    `n_experts`, top-k and the renormalisation are over all of them, and what
    the absent experts would add is LEFT OUT; that partial result goes on to
    the next layer. Embedding and head have `vocab_size` rows (a slice of the
    published 152,576). `moe_layer` summed over every share is the uncut layer
    (tests/test_mimo_v2.py).
  * The three multi-token-prediction layers and the vision and audio towers
    are left out. Input is token ids.
  * Readings of the config that are the writer's (the configuration file lists
    them under `assumed`): `attention_value_scale` multiplies v in both kinds;
    `attention_chunk_size` and `attention_projection_layout` do not change the
    function; rotary is the rotate-half form on the leading channels.
  * The router's correction bias and the sink logits are seeded values.

`round_to` (a dtype) rounds every matrix to that dtype before the float32
cast: the cell's 8-bit reading (`float8_e4m3fn`), which its limits must refuse.
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
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate.T) * (h @ w_up.T)) @ w_down.T


def _rotate(x, base, rot):
    """x (T, heads, d): rotate-half on channels [0, rot), position = row."""
    T = x.shape[0]
    inv = base ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)  # (rot / 2,)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : rot // 2], x[..., rot // 2 : rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def attention_layer(p, h, cfg, window_layer, f=_f32):
    """h (T, D) -> (T, D): one attention layer of the kind `window_layer` says."""
    T = h.shape[0]
    H = cfg["n_head"]
    if window_layer:
        n_kv, dq, dv, base = cfg["swa_n_kv_heads"], cfg["swa_head_dim"], cfg["swa_v_head_dim"], cfg["swa_rope_theta"]
    else:
        n_kv, dq, dv, base = cfg["n_kv_heads"], cfg["head_dim"], cfg["v_head_dim"], cfg["rope_theta"]
    rot = int(dq * cfg["partial_rotary_factor"])
    q = _rotate((h @ f(p.wq).T).reshape(T, H, dq), base, rot)
    k = _rotate((h @ f(p.wk).T).reshape(T, n_kv, dq), base, rot)
    v = cfg["attention_value_scale"] * (h @ f(p.wv).T).reshape(T, n_kv, dv)
    k, v = jnp.repeat(k, H // n_kv, axis=1), jnp.repeat(v, H // n_kv, axis=1)  # head h reads kv head h // group
    j = jnp.arange(T)[None, :]
    out = []
    for r in range(0, T, QUERY_BLOCK):  # rows [r, r + QUERY_BLOCK) against every key
        n = min(QUERY_BLOCK, T - r)
        a = jnp.einsum("ihc,jhc->hij", q[r:r + n], k) / math.sqrt(dq)
        i = r + jnp.arange(n)[:, None]
        visible = (j <= i) & (j > i - cfg["sliding_window"]) if window_layer else j <= i
        a = jnp.where(visible[None], a, -jnp.inf)
        if p.sink is not None:
            sink = jnp.broadcast_to(p.sink.astype(jnp.float32)[:, None, None], (H, n, 1))
            prob = jax.nn.softmax(jnp.concatenate([a, sink], axis=-1), axis=-1)[..., :T]
        else:
            prob = jax.nn.softmax(a, axis=-1)
        out.append(jnp.einsum("hij,jhc->ihc", prob, v).reshape(n, H * dv))
    o = jnp.concatenate(out)
    return o @ f(p.wo).T


def moe_layer(p, h, cfg, f=_f32):
    """h (T, D) -> the part of the routed layer's output that the experts held
    (`p.w_gate`'s leading axis, from `expert_offset`) give."""
    s = jax.nn.sigmoid(h @ p.router.astype(jnp.float32).T)  # (T, n_experts): the router is never rounded
    _, idx = jax.lax.top_k(s + p.router_bias.astype(jnp.float32), cfg["moe_top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    y = jnp.zeros_like(h)
    for e in range(p.w_gate.shape[0]):  # the experts held here
        w_e = jnp.sum(jnp.where(idx == cfg["expert_offset"] + e, w, 0.0), axis=-1)  # (T,), 0 where e was not selected
        y = y + w_e[:, None] * _swiglu(h, f(p.w_gate[e]), f(p.w_up[e]), f(p.w_down[e]))
    return y


def layer(p, x, cfg, i, f=_f32):
    eps = cfg["rms_norm_eps"]
    x = x + attention_layer(p.attn, _rms(x, p.norm1.astype(jnp.float32), eps), cfg, bool(cfg["layer_pattern"][i]), f)
    h = _rms(x, p.norm2.astype(jnp.float32), eps)
    if cfg["moe_layer_freq"][i]:
        return x + moe_layer(p.mlp, h, cfg, f)
    return x + _swiglu(h, f(p.mlp.w_gate), f(p.mlp.w_up), f(p.mlp.w_down))


def logits(params, tokens, cfg, last=None, rows=None, round_to=None):
    """tokens (T,) int -> float32 logits (T, V), or of the `last` positions
    only, or of the positions `rows` (an int array) only. One jitted call a
    layer."""
    f = lambda a: _f32(a, round_to)
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: jnp.take(f(e), t, axis=0))(params.wte, tokens)
        for i, p in enumerate(params.layers):
            x = jax.jit(lambda p, x, i=i: layer(p, x, cfg, i, f))(p, x)
        if last is not None:
            x = x[-last:]
        if rows is not None:
            x = jnp.take(x, jnp.asarray(rows), axis=0)
        head = lambda w, hw, x: _rms(x, w.astype(jnp.float32), cfg["rms_norm_eps"]) @ f(hw).T
        return jax.jit(head)(params.final_norm, params.lm_head, x)
