"""Plain float32 reference of Olmo-Hybrid (Gated-DeltaNet layers, three to every
full-attention layer; the norm on each branch's OUTPUT).

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`:
whole sequences, no cache, no pages, no chunks, no kernel, no batching, a PYTHON
loop over layers. The delta rule runs TOKEN BY TOKEN (`lax.scan` over T, the
state a (d_k, d_v) matrix a head), the short convolution is four shifted
products, the full layers are a causal softmax over all keys, computed a block
of queries at a time so that no (heads, T, T) array is live. It imports nothing
of `midgpt_tpu`: it reads the parameter arrays BY NAME off whatever object holds
them (`params.linear.wq[l]`: the l-th linear layer; `params.full.wq[p]`: the p-th full layer)
and the sizes from a plain dict (`dataclasses.asdict` of the model config). One
jitted call a layer, the layer's matrices cast to float32 inside, so at the
published widths one layer's float32 weights (0.86 GB) are live beside the
served copy.

Source: https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json
(`model_type` olmo_hybrid); the linear layer is the open flash-linear-attention
`GatedDeltaNet` layer whose key names the config carries, as recalled. With
n(x; g) = g * x / sqrt(mean(x^2) + `rms_norm_eps`), on x (T, D):

    h = x + n(mixer(x); norm_attn);  y = h + n((silu(h W_gate^T) * (h W_up^T)) W_down^T; norm_mlp)
    logits = n(y; final_norm) W_head^T

    linear_attention, H heads of d_k keys and d_v values:
        u = [x W_q^T | x W_k^T | x W_v^T]                      (T, H (2 d_k + d_v)), no bias
        c_t = silu(sum_{j<4} taps[:, j] * u_{t-3+j})           zeros before the sequence; the last tap on token t
        q, k, v = c split as u was;  q = q / sqrt(|q|^2 + 1e-6) * d_k^-1/2;  k = k / sqrt(|k|^2 + 1e-6)   a head
        beta = 2 sigmoid(x W_b^T)   (x 2: `allow_neg_eigval`);  g = -exp(A_log) * softplus(x W_a^T + dt_bias)   a head
        S' = exp(g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t                  S_0 = 0
        mixer = (n_dv(o; o_norm) * silu(x W_g^T)) W_o^T        o_norm: d_v gains shared by the heads

    full_attention, n_head heads of head_dim = D / n_head:
        q, k = n(x W_q^T; q_norm), n(x W_k^T; k_norm)          over ALL n_head * head_dim channels, then split
        NO rotary, no other position signal
        mixer = softmax(q k^T / sqrt(head_dim), key j visible to query i iff j <= i) v W_o^T

Readings that are the writer's are listed under `assumed` in the configuration
file beside this one.

`round_to` (a dtype) rounds every matrix (embedding, projections, taps, head;
not the norm gains, not `A_log`, not `dt_bias`) to that dtype before the float32
cast: the cell's 8-bit reading (`float8_e4m3fn`), which its limits must refuse.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128  # queries of a full layer scored at once: (heads, 128, T) float32; the callers pad T to a multiple of 128


def _f32(a, round_to=None):
    if round_to is not None and a.ndim >= 2:
        # behind a barrier: the compiler may drop a narrowing convert that is
        # widened again at once (xla_allow_excess_precision), and on the chip did
        a = jax.lax.optimization_barrier(a.astype(round_to))
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _mlp(p, h, cfg, f):
    y = (jax.nn.silu(h @ f(p.w_gate).T) * (h @ f(p.w_up).T)) @ f(p.w_down).T
    return h + _rms(y, p.norm_mlp, cfg["rms_norm_eps"])


def linear_layer(p, x, cfg, f=_f32):
    """One linear_attention layer and its MLP: x (T, D) -> (T, D)."""
    T = x.shape[0]
    H, dk, dv, K = cfg["linear_heads"], cfg["linear_key_dim"], cfg["linear_value_dim"], cfg["conv_kernel"]
    eps = cfg["rms_norm_eps"]
    u = jnp.concatenate([x @ f(p.wq).T, x @ f(p.wk).T, x @ f(p.wv).T], axis=-1)
    taps = f(p.conv)
    before = jnp.pad(u, ((K - 1, 0), (0, 0)))
    c = jax.nn.silu(sum(before[j : j + T] * taps[:, j] for j in range(K)))
    q, k, v = c[:, : H * dk].reshape(T, H, dk), c[:, H * dk : 2 * H * dk].reshape(T, H, dk), c[:, 2 * H * dk :].reshape(T, H, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / math.sqrt(dk)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(x @ f(p.w_beta).T) * (2.0 if cfg["allow_neg_eigval"] else 1.0)
    g = -jnp.exp(p.a_log.astype(jnp.float32)) * jax.nn.softplus(x @ f(p.w_a).T + p.dt_bias.astype(jnp.float32))

    def token(S, t):
        q_t, k_t, v_t, g_t, b_t = t  # (H, d), (H,)
        S = jnp.exp(g_t)[:, None, None] * S
        S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32), (q, k, v, g, beta))
    o = (_rms(o, p.o_norm, eps).reshape(T, H * dv) * jax.nn.silu(x @ f(p.wg).T)) @ f(p.wo).T
    return _mlp(p, x + _rms(o, p.norm_attn, eps), cfg, f)


def full_layer(p, x, cfg, f=_f32):
    """One full_attention layer and its MLP: x (T, D) -> (T, D); T a multiple of QUERY_BLOCK or under it."""
    T = x.shape[0]
    H, eps = cfg["n_head"], cfg["rms_norm_eps"]
    C = cfg["n_embd"] // H
    q = _rms(x @ f(p.wq).T, p.q_norm, eps).reshape(T, H, C)
    k = _rms(x @ f(p.wk).T, p.k_norm, eps).reshape(T, H, C)
    v = (x @ f(p.wv).T).reshape(T, H, C)
    nb = T // QUERY_BLOCK if T % QUERY_BLOCK == 0 else 1

    def block(args):
        qb, first = args  # (T / nb, H, C), the block's first position
        s = jnp.einsum("ihc,jhc->hij", qb, k) / math.sqrt(C)
        s = jnp.where(jnp.arange(T)[None, :] <= first + jnp.arange(T // nb)[:, None], s, -jnp.inf)
        return jnp.einsum("hij,jhc->ihc", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q.reshape(nb, T // nb, H, C), jnp.arange(nb) * (T // nb))).reshape(T, H * C)
    return _mlp(p, x + _rms(o @ f(p.wo).T, p.norm_attn, eps), cfg, f)


def forward(params, tokens, cfg, rows=None, round_to=None):
    """tokens (T,) int -> float32 logits (T, V), or of the positions `rows` (an int array) only."""
    f = lambda a: _f32(a, round_to)
    types = list(cfg["layer_types"])[: cfg["n_layer"]]
    period = types.index("full_attention") + 1
    with jax.default_matmul_precision("highest"):
        linear = jax.jit(lambda layers, i, x: linear_layer(jax.tree.map(lambda a: a[i], layers), x, cfg, f))
        full = jax.jit(lambda layers, i, x: full_layer(jax.tree.map(lambda a: a[i], layers), x, cfg, f))
        x = jax.jit(lambda e, t: jnp.take(f(e), t, axis=0))(params.wte, tokens)
        for l, kind in enumerate(types):
            i, j = divmod(l, period)  # the i-th full layer; linear layer i * (period - 1) + j
            x = full(params.full, i, x) if kind == "full_attention" else linear(params.linear, i * (period - 1) + j, x)
        if rows is not None:
            x = jnp.take(x, jnp.asarray(rows), axis=0)
        return jax.jit(lambda g, hw, x: _rms(x, g, cfg["rms_norm_eps"]) @ f(hw).T)(params.final_norm, params.lm_head, x)


def logits(params, tokens, cfg, rows=None, round_to=None):
    """`forward` under the name `serve_family_cell.py` calls."""
    return forward(params, tokens, cfg, rows=rows, round_to=round_to)
