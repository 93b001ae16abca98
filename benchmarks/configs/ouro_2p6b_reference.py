"""Plain float32 reference of Ouro (a LOOPED transformer: one stack of layers
applied `n_loop` times with the same weights, the final norm and an exit gate
after every pass).

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`:
whole sequences, no cache, no pages, no kernel, no batching, a PYTHON loop over
passes and layers, an explicit (T, T) causal mask. Nothing is kept between
passes but the hidden state: pass r's keys and values are pass r's own, made
and used inside one layer call, which is what the program's cache of
`n_loop * n_layer` layers has to reproduce. It imports nothing of `midgpt_tpu`:
it reads the parameter arrays BY NAME off whatever object holds them
(`params.layers.wq[l]`: every layer leaf stacked over layers, ...) and the
sizes from a plain dict (`dataclasses.asdict` of the model config). One jitted
call a layer application, the layer's matrices cast to float32 inside, so at
the published widths one layer's float32 weights (206 MB) are live beside the
served copy.

Source of the equations: https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
and the published modelling code as recalled (arXiv:2510.25741). With
n(x; g) = g * x / sqrt(mean(x^2) + `rms_norm_eps`):

    h = E[t]
    for r in 1..n_loop:
        x = h
        for l in 1..n_layer:
            a = n(x; norm_in);  q, k, v = a W_q^T, a W_k^T, a W_v^T as (n_head, head_dim), no bias
            rotate-half rotary on q and k over ALL head_dim channels, angle
                pos * `rope_theta`^(-2i / head_dim) for the channel pair (i, i + head_dim / 2)
            o = softmax(q k^T / sqrt(head_dim), key j visible to query i iff j <= i) v
            x = x + n(o W_o^T; norm_post_attn)               # the sublayer's OUTPUT is normed
            m = n(x; norm_pre_mlp)
            x = x + n((silu(m W_gate^T) * (m W_up^T)) W_down^T; norm_post_mlp)
        h = n(x; final_norm)                     # after EVERY pass; the next pass starts from it
        lambda_r = sigmoid(exit_w . h + exit_b)
    p_r = lambda_r prod_{j<r} (1 - lambda_j) for r < n_loop;  p_{n_loop} = prod_{j<n_loop} (1 - lambda_j)
    logits = h W_head^T

At `early_exit_threshold` 1 no pass before the last is the last: the logits are
the last pass's, and `p` is returned beside them (`forward`).

Readings that are the writer's (the configuration file lists them under
`assumed`): no projection biases; the four norms' placement; the gate reads the
NORMED state and has a bias; rotate-half pairing.

`round_to` (a dtype) rounds every matrix (embedding, projections, head; not the
norm gains, not the gate) to that dtype before the float32 cast: the cell's
8-bit reading (`float8_e4m3fn`), which its limits must refuse.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _f32(a, round_to=None):
    if round_to is not None and a.ndim >= 2:
        # behind a barrier: the compiler may drop a narrowing convert that is
        # widened again at once (xla_allow_excess_precision), and on the chip did
        a = jax.lax.optimization_barrier(a.astype(round_to))
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rotate(x, base):
    """x (T, heads, C): rotate-half over all C channels, position = row."""
    T, C = x.shape[0], x.shape[-1]
    inv = base ** (-jnp.arange(0, C, 2, dtype=jnp.float32) / C)  # (C / 2,)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : C // 2], x[..., C // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer(p, x, cfg, f=_f32):
    """One application of one layer: x (T, D) -> (T, D). `p`: the layer's own arrays."""
    T = x.shape[0]
    H, C, eps = cfg["n_head"], cfg["head_dim"], cfg["rms_norm_eps"]
    a = _rms(x, p.norm_in, eps)
    q = _rotate((a @ f(p.wq).T).reshape(T, H, C), cfg["rope_theta"])
    k = _rotate((a @ f(p.wk).T).reshape(T, H, C), cfg["rope_theta"])
    v = (a @ f(p.wv).T).reshape(T, H, C)
    s = jnp.einsum("ihc,jhc->hij", q, k) / math.sqrt(C)
    s = jnp.where(jnp.arange(T)[None, :] <= jnp.arange(T)[:, None], s, -jnp.inf)
    o = jnp.einsum("hij,jhc->ihc", jax.nn.softmax(s, axis=-1), v).reshape(T, H * C)
    x = x + _rms(o @ f(p.wo).T, p.norm_post_attn, eps)
    m = _rms(x, p.norm_pre_mlp, eps)
    return x + _rms((jax.nn.silu(m @ f(p.w_gate).T) * (m @ f(p.w_up).T)) @ f(p.w_down).T, p.norm_post_mlp, eps)


def end_of_pass(g, w, b, x, cfg):
    """x (T, D) after a pass's last layer -> (the normed state h (T, D), the exit gate's lambda (T,))."""
    h = _rms(x, g, cfg["rms_norm_eps"])
    return h, jax.nn.sigmoid(h @ w.astype(jnp.float32) + b)


def forward(params, tokens, cfg, rows=None, round_to=None, n_loop=None):
    """tokens (T,) int -> (float32 logits (T, V), exit distribution p (T,
    n_loop)), or of the positions `rows` (an int array) only. `n_loop`: passes
    to run, where not the configuration's."""
    f = lambda a: _f32(a, round_to)
    n_loop = cfg["n_loop"] if n_loop is None else n_loop
    with jax.default_matmul_precision("highest"):
        one = jax.jit(lambda layers, l, x: layer(jax.tree.map(lambda a: a[l], layers), x, cfg, f))
        end = jax.jit(lambda g, w, b, x: end_of_pass(g, w, b, x, cfg))
        h = jax.jit(lambda e, t: jnp.take(f(e), t, axis=0))(params.wte, tokens)
        left, p = jnp.ones(tokens.shape[0], jnp.float32), []
        for r in range(n_loop):  # the SAME layers each pass
            x = h
            for l in range(cfg["n_layer"]):
                x = one(params.layers, l, x)
            h, lam = end(params.final_norm, params.exit_w, params.exit_b, x)
            p.append(left if r == n_loop - 1 else lam * left)
            left = left * (1.0 - lam)
        p = jnp.stack(p, axis=-1)
        if rows is not None:
            h, p = jnp.take(h, jnp.asarray(rows), axis=0), jnp.take(p, jnp.asarray(rows), axis=0)
        return jax.jit(lambda hw, h: h @ f(hw).T)(params.lm_head, h), p


def logits(params, tokens, cfg, rows=None, round_to=None):
    """`forward`'s logits alone (what `serve_family_cell.py` compares)."""
    return forward(params, tokens, cfg, rows=rows, round_to=round_to)[0]
