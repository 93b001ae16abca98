"""Plain reference forward of the midGPT architecture: straightforward
`jax.numpy`, float32, matmul precision "highest", no kernels, no cache, no
batching tricks. Independent of `midgpt_tpu.models`: it reads only the
parameter arrays (by attribute name) and the sizes.

Architecture as published in midGPT `src/model.py` / `src/layers.py`:
weightless RMSNorm (eps 1e-6) before attention and before the MLP; one fused
QKV projection; LayerNorm over each head's channels of q and k with a learned
scale and no bias (eps 1e-6); GPT-J interleaved RoPE (base 10000); causal
softmax attention scaled by 1/sqrt(head_dim); output projection; GELU (tanh
form) MLP of width 4D; residuals around both; final weightless RMSNorm (eps
1e-5); untied-after-init lm_head. Dense multi-head attention only: a
configuration with grouped KV heads, experts or a sliding window needs its own
reference beside its configuration file.

Weights come in the dtype they are served or trained in and are upcast here,
layer by layer, so the reference sees exactly the stored values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _head_ln(x, scale, eps=1e-6):
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(jnp.square(c), axis=-1, keepdims=True) + eps) * scale


def _rope(x):
    """x (B, T, H, C): rotate interleaved pairs (2i, 2i+1) by t * base^(-2i/C)."""
    T, C = x.shape[1], x.shape[-1]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, C, 2, dtype=jnp.float32) / C))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]  # (T, C/2)
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack((a * cos - b * sin, a * sin + b * cos), axis=-1).reshape(x.shape)


def logits(params, tokens, n_head: int):
    """tokens (B, T) int -> logits (B, T, V) float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = f32(params.wte)[tokens]
        B, T, D = x.shape
        C = D // n_head
        mask = jnp.tril(jnp.ones((T, T), bool))
        n_layer = params.blocks.attn.wo.shape[0]
        for l in range(n_layer):
            a, m = params.blocks.attn, params.blocks.mlp
            if getattr(a, "wkv", None) is not None:
                raise NotImplementedError("reference.py covers dense multi-head attention only")
            h = _rms(x, 1e-6)
            wqkv = f32(a.wqkv[l]).reshape(3 * D, D)
            q, k, v = jnp.split(h @ wqkv.T, 3, axis=-1)
            q = _rope(_head_ln(q.reshape(B, T, n_head, C), f32(a.q_scale[l])))
            k = _rope(_head_ln(k.reshape(B, T, n_head, C), f32(a.k_scale[l])))
            v = v.reshape(B, T, n_head, C)
            s = jnp.einsum("bqhc,bkhc->bhqk", q, k) / jnp.sqrt(jnp.float32(C))
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            att = jnp.einsum("bhqk,bkhc->bqhc", p, v).reshape(B, T, D)
            x = x + att @ f32(a.wo[l]).T
            h = _rms(x, 1e-6)
            x = x + jax.nn.gelu(h @ f32(m.w_up[l]).T, approximate=True) @ f32(m.w_down[l]).T
        x = _rms(x, 1e-5)
        return x @ f32(params.lm_head).T


def token_losses(params, x, y, n_head: int):
    """Next-token cross-entropy of tokens x (B, T) against labels y (B, T), per token: (B, T) float32."""
    lg = logits(params, x, n_head)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
    return lse - picked
