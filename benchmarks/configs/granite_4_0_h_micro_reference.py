"""Plain float32 reference of granite-4.0-h-micro (Mamba-2 state-space layers,
nine to every grouped-query attention layer, under the family's four
multipliers; the norm on each branch's INPUT; the head tied to the embedding).

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`:
whole sequences, no cache, no pages, no chunks, no kernel, no batching, a PYTHON
loop over layers. The state-space recurrence runs TOKEN BY TOKEN (`lax.scan`
over T, the state a (P, N) matrix a head), so it is independent of the chunked
form it judges; the short convolution is four shifted products and a bias; the
attention layers are a causal softmax over all keys, computed a block of
queries at a time so that no (heads, T, T) array is live. It imports nothing of
`midgpt_tpu`: it reads the parameter arrays BY NAME off whatever object holds
them (`params.mamba.w_z[l]`: the l-th mamba layer; `params.attn.wq[p]`: the p-th
attention layer) and the sizes from a plain dict (`dataclasses.asdict` of the
model config). One jitted call a layer, the layer's matrices cast to float32
inside, so at the published widths one layer's float32 weights (0.30 GB; the
tied head's 0.82 GB at the end) are live beside the served copy and the 12.8
GB of float32 weights are never held at once.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json
(`model_type` granitemoehybrid); the mamba layer is the open Mamba-2 layer
whose key names the config carries, as recalled. With n(x; g) = g * x /
sqrt(mean(x^2) + `rms_norm_eps`), r = `residual_multiplier`, on x (T, D):

    x_0 = embedding_multiplier * E[token]
    h = x + r * mixer(n(x; norm_in));  y = h + r * (silu(u W_gate^T) * (u W_up^T)) W_down^T,  u = n(h; norm_mlp)
    logits = n(y; final_norm) E^T / logits_scaling

    mamba, H heads of P channels, N = mamba_state, u = n(x; norm_in):
        z = u W_z^T (T, H P);  xBC = u W_xbc^T (T, H P + 2 N);  dt = u W_dt^T (T, H): `in_proj`'s three row blocks, no bias
        c_t = silu(sum_{j<4} taps[:, j] * xBC_{t-3+j} + conv_bias)     zeros before the sequence; the last tap on token t
        x, B, C = c split (H P | N | N): ONE B and ONE C for all heads
        dt = softplus(dt + dt_bias);  a_t = dt_t * A,  A = -exp(A_log)                     a head, no clamp
        h_t = exp(a_t) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t                    h_0 = 0, (P, N) a head
        mixer = n_HP(y * silu(z); gate_norm) W_out^T           the gate BEFORE the norm, ONE norm over all H P channels

    attention, n_head query heads on n_kv_head K/V heads of head_dim = D / n_head (query head h reads K/V head h // groups):
        q, k, v = u W_q^T, u W_k^T, u W_v^T                    no bias, no norm, NO rotary, no other position signal
        mixer = softmax(attention_multiplier * q k^T, key j visible to query i iff j <= i) v W_o^T

Readings that are the writer's are listed under `assumed` in the configuration
file beside this one.

`round_to` (a dtype) rounds every matrix (embedding, projections, taps; not
the norm gains, not the taps' bias, not `A_log`, `D`, `dt_bias`) to that dtype
before the float32 cast: the cell's 8-bit reading (`float8_e4m3fn`), which its
limits must refuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128  # queries of an attention layer scored at once: (heads, 128, T) float32; the callers pad T to a multiple of 128


def _f32(a, round_to=None):
    if round_to is not None and a.ndim >= 2:
        # behind a barrier: the compiler may drop a narrowing convert that is
        # widened again at once (xla_allow_excess_precision), and on the chip did
        a = jax.lax.optimization_barrier(a.astype(round_to))
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _mlp(p, h, cfg, f):
    u = _rms(h, p.norm_mlp, cfg["rms_norm_eps"])
    return h + cfg["residual_multiplier"] * ((jax.nn.silu(u @ f(p.w_gate).T) * (u @ f(p.w_up).T)) @ f(p.w_down).T)


def mamba_layer(p, x, cfg, f=_f32):
    """One mamba layer and its MLP: x (T, D) -> (T, D)."""
    T = x.shape[0]
    H, P, N, K = cfg["mamba_heads"], cfg["mamba_head_dim"], cfg["mamba_state"], cfg["mamba_conv"]
    eps = cfg["rms_norm_eps"]
    u = _rms(x, p.norm_in, eps)
    z, xbc, dt = u @ f(p.w_z).T, u @ f(p.w_xbc).T, u @ f(p.w_dt).T
    taps = f(p.conv)
    before = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    c = jax.nn.silu(sum(before[j : j + T] * taps[:, j] for j in range(K)) + p.conv_bias.astype(jnp.float32))
    xs, Bs, Cs = c[:, : H * P].reshape(T, H, P), c[:, H * P : H * P + N], c[:, H * P + N :]
    dt = jax.nn.softplus(dt + p.dt_bias.astype(jnp.float32))
    A = -jnp.exp(p.a_log.astype(jnp.float32))
    D = p.d_skip.astype(jnp.float32)

    def token(h, t):
        x_t, B_t, C_t, dt_t = t  # (H, P), (N,), (N,), (H,)
        h = jnp.exp(dt_t * A)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        return h, jnp.einsum("hpn,n->hp", h, C_t) + D[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32), (xs, Bs, Cs, dt))
    o = _rms(y.reshape(T, H * P) * jax.nn.silu(z), p.gate_norm, eps) @ f(p.w_out).T
    return _mlp(p, x + cfg["residual_multiplier"] * o, cfg, f)


def attention_layer(p, x, cfg, f=_f32):
    """One attention layer and its MLP: x (T, D) -> (T, D); T a multiple of QUERY_BLOCK or under it."""
    T = x.shape[0]
    H, Hkv = cfg["n_head"], cfg["n_kv_head"]
    C = cfg["n_embd"] // H
    u = _rms(x, p.norm_in, cfg["rms_norm_eps"])
    q = (u @ f(p.wq).T).reshape(T, H, C)
    k = jnp.repeat((u @ f(p.wk).T).reshape(T, Hkv, C), H // Hkv, axis=1)  # query head h reads K/V head h // groups
    v = jnp.repeat((u @ f(p.wv).T).reshape(T, Hkv, C), H // Hkv, axis=1)
    nb = T // QUERY_BLOCK if T % QUERY_BLOCK == 0 else 1

    def block(args):
        qb, first = args  # (T / nb, H, C), the block's first position
        s = jnp.einsum("ihc,jhc->hij", qb, k) * cfg["attention_multiplier"]
        s = jnp.where(jnp.arange(T)[None, :] <= first + jnp.arange(T // nb)[:, None], s, -jnp.inf)
        return jnp.einsum("hij,jhc->ihc", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q.reshape(nb, T // nb, H, C), jnp.arange(nb) * (T // nb))).reshape(T, H * C)
    return _mlp(p, x + cfg["residual_multiplier"] * (o @ f(p.wo).T), cfg, f)


def forward(params, tokens, cfg, rows=None, round_to=None):
    """tokens (T,) int -> float32 logits (T, V), or of the positions `rows` (an int array) only."""
    f = lambda a: _f32(a, round_to)
    types = list(cfg["layer_types"])[: cfg["n_layer"]]
    with jax.default_matmul_precision("highest"):
        mamba = jax.jit(lambda layers, i, x: mamba_layer(jax.tree.map(lambda a: a[i], layers), x, cfg, f))
        attn = jax.jit(lambda layers, i, x: attention_layer(jax.tree.map(lambda a: a[i], layers), x, cfg, f))
        x = jax.jit(lambda e, t: cfg["embedding_multiplier"] * jnp.take(f(e), t, axis=0))(params.wte, tokens)
        seen = {"mamba": 0, "attention": 0}  # layers of each kind so far: the index into that kind's stacked leaves
        for kind in types:
            x = (attn if kind == "attention" else mamba)(params.attn if kind == "attention" else params.mamba, seen[kind], x)
            seen[kind] += 1
        if rows is not None:
            x = jnp.take(x, jnp.asarray(rows), axis=0)
        return jax.jit(lambda g, e, x: _rms(x, g, cfg["rms_norm_eps"]) @ f(e).T / cfg["logits_scaling"])(
            params.final_norm, params.wte, x)


def logits(params, tokens, cfg, rows=None, round_to=None):
    """`forward` under the name `serve_family_cell.py` calls."""
    return forward(params, tokens, cfg, rows=rows, round_to=round_to)
