"""Plain float32 reference of Trinity (AFMoE): sliding-window layers with rotary
beside global layers with no position signal, QK-norm, an output gate on
attention, sandwich norms, a muP-scaled embedding, sigmoid-routed experts with
a selection bias beside a shared expert; as one pipeline stage of whole layers
holds it.

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`:
whole sequences, no cache, no pages, no kernel, no batching; the causal and the
banded mask written as masks (QUERY_BLOCK rows of the (T, T) mask at a time,
each against every key, so that a few thousand tokens' scores fit beside the
weights); rotary applied by the layer's kind; the experts by a loop over the
experts held. It imports nothing of `midgpt_tpu`: it reads the parameter arrays
BY NAME off whatever object holds them (`params.layers[i].attn.wq`, ...) and the
sizes from a plain dict (`dataclasses.asdict` of the model config). It runs half
a layer at a time (two jitted calls a layer) and casts an expert's matrices to
float32 inside the loop over experts, so that at the published widths no more
than one layer's float32 weights (3.36 GB for an expert layer) could be live at
once, and in practice one expert's.

Source of the equations: the published config
(https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json,
`model_type: afmoe`) and the `afmoe` modelling code of `transformers` as
recalled. RMSNorm_w carries a gain, eps `rms_norm_eps`, everywhere:

    x = E[t] * sqrt(n_embd)   (`mup_enabled`)
    h = x + RMSNorm_w(Attn_i(RMSNorm_w(x)));  x' = h + RMSNorm_w(FFN_i(RMSNorm_w(h)))
    logits = W_head RMSNorm_w(x)   (untied)

Attention, with u the normed input: q = W_q u as (n_head, head_dim), k = W_k u
and v = W_v u as (n_kv_heads, head_dim), g = W_g u as (n_head, head_dim); q and
k RMS-normed over head_dim with a gain (before any rotation); q head h reads
K/V head h // (n_head / n_kv_heads); a = q.k / sqrt(head_dim); plain softmax;
out = W_o (o * sigmoid(g)).
  `sliding_attention`: rotate-half rotary over ALL head_dim channels of q and k
    (pair (i, i + head_dim / 2), angle pos * rope_theta^(-2i / head_dim)); key j
    visible to query i iff i - `sliding_window` < j <= i.
  `full_attention`: NO rotation, no position signal; visible iff j <= i.
FFN: layer i < `n_dense_layers`: SwiGLU W_down(SiLU(W_gate u) * W_up u). Else s =
sigmoid(W_r u) over `n_experts`, the `moe_top_k` largest of s + expert_bias
selected, weights the selected s (without the bias) over their sum + 1e-20
(`route_norm`), times `route_scale`; y = SwiGLU_shared(u) + sum_e w_e SwiGLU_e(u).

Departures from the published model, each on purpose:
  * ONE STAGE'S LAYERS. The configuration keeps 5 of 32 layers (`n_layer`) with
    ONE leading dense layer (`n_dense_layers` 2 -> 1); `layer_types` is read by
    index as published. All 128 experts are held in the benchmark's cell; where
    fewer are (`p.w_gate`'s leading axis, from `expert_offset`: the tests' share
    test), the router scores all `n_experts`, top-k and the renormalisation are
    over all of them, and what the absent experts would add is LEFT OUT.
    `moe_layer` summed over every share, the shared expert counted once, is the
    uncut layer (tests/test_trinity.py).
  * What no config key states is the writer's reading and is listed in the
    configuration file under `assumed`: where the gate sits, QK-norm before
    rotation, no position signal in full layers, the rotate-half pairing, the
    sandwich placement, the muP factor, no biases.
  * `expert_bias` is a buffer a balancing rule moves during training
    (`load_balance_coeff`: not run); it is read as it is seeded.

`round_to` (a dtype) rounds every matrix to that dtype before the float32
cast: the cell's 8-bit reading (`float8_e4m3fn`), which its limits must refuse.

ONE TOKEN AGAIN, UNDER ANOTHER CHOICE OF EXPERTS (`logits(keep=[])`,
`token_attention`, `token_scores`, `token_experts`, `token_logits`). The top-k
is not continuous: where the 8th and 9th of `s + expert_bias` lie closer than a
lower precision's error in s, a program that is right picks the other one, and
its logits at that token then differ from these by a whole expert's weight.
What such a program must equal is THIS reference under that other choice. So
the full forward can keep every layer's input stream, and a token's row can be
run again from any layer on against the kept streams of the other tokens (their
own choices as the full forward made them), its experts `chosen` by the caller:
the same `attention_layer` (one query row) and `moe_layer` (one token), nothing
else. With the reference's own choices the row comes out as the full forward's
(tests/test_trinity.py). benchmarks/serve_routed_cell.py walks the choices.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256  # query rows whose (heads, rows, T) scores are live at once
SLIDING = "sliding_attention"


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


def _rotate(x, base, pos):
    """x (n, heads, d) at positions `pos` (n,): rotate-half over all d channels."""
    d = x.shape[-1]
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # (d / 2,)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention_layer(p, u, cfg, sliding, f=_f32, gate=True, row=None):
    """u (T, D), the normed input -> (T, D): one attention layer, `sliding` or
    full. `gate` False leaves the output gate out (the tests' control). `row` (a
    traced index): the query at that position alone, against every key -> (1, D)."""
    T = u.shape[0]
    H, n_kv, d, eps = cfg["n_head"], cfg["n_kv_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    uq, pos_q = (u, jnp.arange(T)) if row is None else (jax.lax.dynamic_slice_in_dim(u, row, 1), row + jnp.arange(1))
    q = _rms((uq @ f(p.wq).T).reshape(-1, H, d), p.q_norm, eps)
    k = _rms((u @ f(p.wk).T).reshape(T, n_kv, d), p.k_norm, eps)
    v = (u @ f(p.wv).T).reshape(T, n_kv, d)
    if sliding:
        q, k = _rotate(q, cfg["rope_theta"], pos_q), _rotate(k, cfg["rope_theta"], jnp.arange(T))
    k, v = jnp.repeat(k, H // n_kv, axis=1), jnp.repeat(v, H // n_kv, axis=1)  # head h reads kv head h // group
    j = jnp.arange(T)[None, :]
    out = []
    for r in range(0, q.shape[0], QUERY_BLOCK):  # QUERY_BLOCK query rows at a time against every key
        i = pos_q[r:r + QUERY_BLOCK, None]
        a = jnp.einsum("ihc,jhc->hij", q[r:r + QUERY_BLOCK], k) / math.sqrt(d)
        visible = (j <= i) & (j > i - cfg["sliding_window"]) if sliding else j <= i
        prob = jax.nn.softmax(jnp.where(visible[None], a, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hij,jhc->ihc", prob, v).reshape(-1, H * d))
    o = jnp.concatenate(out)
    if gate:
        o = o * jax.nn.sigmoid(uq @ f(p.wg).T)
    return o @ f(p.wo).T


def router_scores(p, u):
    """u (T, D) -> (s, s + expert_bias), both (T, n_experts): what weighs and what selects."""
    s = jax.nn.sigmoid(u @ p.router.astype(jnp.float32).T)  # the router is never rounded
    return s, s + p.expert_bias.astype(jnp.float32)


def moe_layer(p, u, cfg, f=_f32, include_shared=True, chosen=None):
    """u (T, D) -> the part of the expert layer's output that the experts held
    (`p.w_gate`'s leading axis, from `expert_offset`) give, plus the shared
    expert. `chosen` (T, moe_top_k) int: those experts in the top-k's place
    (their weights still from s: module docstring, "one token again")."""
    s, selects = router_scores(p, u)
    idx = jax.lax.top_k(selects, cfg["moe_top_k"])[1] if chosen is None else chosen
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["route_scale"]
    y = _swiglu(u, p.shared, f) if include_shared and p.shared is not None else jnp.zeros_like(u)

    def one(e, y):  # the experts held here, each over every token, masked by its pair weight
        w_e = jnp.sum(jnp.where(idx == cfg["expert_offset"] + e, w, 0.0), axis=-1)  # (T,), 0 where e was not selected
        up = jax.nn.silu(u @ f(p.w_gate[e]).T) * (u @ f(p.w_up[e]).T)
        return y + w_e[:, None] * (up @ f(p.w_down[e]).T)

    return jax.lax.fori_loop(0, p.w_gate.shape[0], one, y)


def attention_half(p, x, cfg, i, f=_f32):
    eps = cfg["rms_norm_eps"]
    o = attention_layer(p.attn, _rms(x, p.norm_in, eps), cfg, cfg["layer_types"][i] == SLIDING, f)
    return x + _rms(o, p.norm_post_attn, eps)


def mlp_half(p, x, cfg, i, f=_f32):
    eps = cfg["rms_norm_eps"]
    u = _rms(x, p.norm_pre_mlp, eps)
    y = _swiglu(u, p.mlp, f) if i < cfg["n_dense_layers"] else moe_layer(p.mlp, u, cfg, f)
    return x + _rms(y, p.norm_post_mlp, eps)


def layer(p, x, cfg, i, f=_f32):
    return mlp_half(p, attention_half(p, x, cfg, i, f), cfg, i, f)


def logits(params, tokens, cfg, last=None, rows=None, round_to=None, keep=None):
    """tokens (T,) int -> float32 logits (T, V), or of the `last` positions
    only, or of the positions `rows` (an int array) only. Two jitted calls a
    layer. `keep` (a list): every layer's input stream (T, D) is appended to it."""
    f = lambda a: _f32(a, round_to)
    scale = math.sqrt(cfg["n_embd"]) if cfg["mup_enabled"] else 1.0
    with jax.default_matmul_precision("highest"):
        # the rows are taken before the cast (rounding is elementwise: the same values): the table is 1.6 GB in float32
        x = jax.jit(lambda e, t: f(jnp.take(e, t, axis=0)) * scale)(params.wte, tokens)
        for i, p in enumerate(params.layers):
            if keep is not None:
                keep.append(x)
            x = jax.jit(lambda p, x, i=i: attention_half(p, x, cfg, i, f))(p, x)
            x = jax.jit(lambda p, x, i=i: mlp_half(p, x, cfg, i, f))(p, x)
        if last is not None:
            x = x[-last:]
        if rows is not None:
            x = jnp.take(x, jnp.asarray(rows), axis=0)
        head = lambda w, hw, x: _rms(x, w, cfg["rms_norm_eps"]) @ f(hw).T
        return jax.jit(head)(params.final_norm, params.lm_head, x)


# ---- one token again, under another choice of experts (module docstring) ----


@functools.partial(jax.jit, static_argnums=(4, 5))
def _token_attention(p, stream, x_t, t, sliding, cfg_key):
    cfg = dict(cfg_key)
    with jax.default_matmul_precision("highest"):
        u = _rms(jax.lax.dynamic_update_slice_in_dim(stream, x_t[None], t, 0), p.norm_in, cfg["rms_norm_eps"])
        o = attention_layer(p.attn, u, cfg, sliding, row=t)
        return x_t + _rms(o, p.norm_post_attn, cfg["rms_norm_eps"])[0]


def token_attention(p, stream, x_t, t, cfg, i):
    """Layer i's attention half for the token at position t alone: `stream` (T,
    D) is the layer's kept input, x_t (D,) this token's own input to it (the
    kept one, or what another choice in an earlier layer made of it) -> (D,)."""
    return _token_attention(p, stream, x_t, t, cfg["layer_types"][i] == SLIDING, _hashable(cfg))


@functools.partial(jax.jit, static_argnums=(2,))
def _token_scores(p, h_t, eps):
    with jax.default_matmul_precision("highest"):
        return router_scores(p.mlp, _rms(h_t[None], p.norm_pre_mlp, eps))[1][0]


def token_scores(p, h_t, cfg):
    """h_t (D,) out of a routed layer's attention half -> `s + expert_bias` (n_experts,), what its router selects by."""
    return _token_scores(p, h_t, cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(3,))
def _token_experts(p, h_t, chosen, cfg_key):
    cfg = dict(cfg_key)
    with jax.default_matmul_precision("highest"):
        y = moe_layer(p.mlp, _rms(h_t[None], p.norm_pre_mlp, cfg["rms_norm_eps"]), cfg, chosen=chosen[None])
        return h_t + _rms(y, p.norm_post_mlp, cfg["rms_norm_eps"])[0]


def token_experts(p, h_t, chosen, cfg):
    """A routed layer's MLP half for one token with the experts `chosen` (moe_top_k,) -> (D,)."""
    return _token_experts(p, h_t, jnp.asarray(chosen, jnp.int32), _hashable(cfg))


@functools.partial(jax.jit, static_argnums=(3,))
def _token_logits(final_norm, lm_head, x_t, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x_t[None], final_norm, eps)[0] @ _f32(lm_head).T


def token_logits(params, x_t, cfg):
    """x_t (D,) out of the last layer -> its logits (V,)."""
    return _token_logits(params.final_norm, params.lm_head, x_t, cfg["rms_norm_eps"])


def _hashable(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()))
