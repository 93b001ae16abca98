"""Plain float32 reference of dots3-note-prev's language model (latent attention
of two geometries: full layers whose indexer selects the top `index_topk`
cached tokens a query, sliding layers over a window of 513 with a wider latent;
a headwise gate; sigmoid-routed experts beside a shared expert), as one chip's
share of an expert-parallel deployment holds it.

Straightforward `jax.numpy`, float32, `jax.default_matmul_precision("highest")`:
whole sequences, no cache, no pages, no kernel, no batching, and NO ABSORPTION:
attention is the published, EXPANDED form, K and V of every head made from the
latent (`W_kvb c`), the causal and the banded mask written as masks, the
indexer's I as a (T, T) matrix in equal blocks of at most QUERY_BLOCK rows, `jax.lax.top_k` per
row (exact; equal scores: the lower position first), the selection as a mask on
the scores, the experts by a loop over the experts held. It imports nothing of
`midgpt_tpu`: it reads the parameter arrays BY NAME off whatever object holds
them (`params.layers[i].attn.w_qa`, ...) and the sizes from a plain dict
(`dataclasses.asdict` of the model config). It runs HALF a layer at a time (one
jitted call for the attention, one for the FFN, the matrices cast to float32
inside), so that at the published widths at most one half's float32 weights
are live beside the served copy.

Source of the equations: the published config
(https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json); the
indexer as the DeepSeek-V3.2 report describes "DSA". With n(.) an RMSNorm
carrying a gain, eps `rms_norm_eps`, u = n_1(x):

    x = E[t];  h = x + Attn(n_1(x));  x' = h + FFN(n_2(h));  final n;  untied head

Attn (both kinds, each with its OWN ranks, head count, widths and rotary base:
the `swa_*` keys for a `sliding_attention` layer): c_q = r_q n_q(W_qa u); q =
W_qb c_q as (heads, nope + rope); [c_kv; k_r] = W_kva u; c = r_kv n_kv(c_kv);
[k_n; v] = W_kvb c as (heads, nope + v); rotate-half rotary on q's last `rope`
channels and on the ONE k_r every head shares, angle pos * theta^(-2i / rope)
for the channel pair (i, i + rope / 2); a = (q_n . k_n + q_r . k_r) / sqrt(nope
+ rope); softmax over the visible keys; values v; g = sigmoid(W_g u), ONE scalar
a head; out = W_o [g_h o_h]. r_q = sqrt(n_embd / q_rank), r_kv = sqrt(n_embd /
kv_rank) where `mla_rescale`.
  full layer: key s visible to query t iff s <= t AND s in S_t, the min(t + 1,
    `index_topk`) positions with the largest I[t, s] = sum_h w[t, h] relu(q^I[t,
    h] . k^I[s]), ties to the lower position; q^I = W_iq c_q as (`index_n_heads`,
    `index_head_dim`), k^I = LayerNorm(W_ik u) (weight and bias, eps 1e-6), both
    rotated on their leading `qk_rope_head_dim` channels with the layer's base,
    w = W_iw u / sqrt(index_n_heads x index_head_dim).
  sliding layer: key s visible iff t - `sliding_window` < s <= t.
FFN: layer i < `n_dense_layers`: SwiGLU W_down(SiLU(W_gate h) * W_up h). Else
shared(h) + sum_k w_k expert_k(h): s = sigmoid(W_r h) over `n_experts`, the
`moe_top_k` largest of s + bias selected, weights the selected s over their sum
+ 1e-20 (`moe_renormalize`) times `routed_scaling_factor`.

`select="dense"` turns the selection OFF (S_t = every s <= t): the cell's second
control, which its limits must refuse on rows whose context passes
`index_topk`; nothing the program may do.

Departures from the published model, each on purpose:
  * ONE CHIP'S SHARE. Only experts [expert_offset, expert_offset + n_held) are
    held (n_held = the expert weights' leading axis); the router scores all
    `n_experts`, top-k and the renormalisation are over all of them, and what
    the absent experts would add is LEFT OUT; the shared expert is whole.
    Embedding and head have `vocab_size` rows (a slice of the published
    152,064). The routed parts of every share plus the shared expert ONCE are
    the uncut layer (tests/test_dots3.py).
  * Only the language model: the vision and audio towers and the MTP module
    are left out.
  * The indexer's Hadamard rotation of q^I and k^I is left out (orthogonal: q.k
    is unchanged) and so is its FP8 storage (a kernel's format).
  * Readings of the config that are the writer's (the configuration file lists
    them under `assumed`): pre-norm placement, the two rescale constants, the
    gate's input and place, the indexer's wiring, the window's reading of 513,
    rotate-half pairing, one routing group, the bias seeded 0.

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


def geometry(cfg, sliding):
    """(heads, q rank, kv rank, nope, rope, v, rotary base) of a layer's kind."""
    pre = "swa_" if sliding else ""
    return (cfg["swa_n_head" if sliding else "n_head"], cfg[pre + "q_lora_rank"], cfg[pre + "kv_lora_rank"],
            cfg[pre + "qk_nope_head_dim"], cfg[pre + "qk_rope_head_dim"], cfg[pre + "v_head_dim"], cfg[pre + "rope_theta"])


def index_scores(p, u, c_q, cfg, base, r0, n, f=_f32):
    """I[t, s] for the query rows [r0, r0 + n) against every key: (n, T) float32."""
    T = u.shape[0]
    Hi, Ci, rot = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    qi = (c_q @ f(p.w_q).T).reshape(T, Hi, Ci)
    qi = jnp.concatenate([_rotate(qi[..., :rot], base), qi[..., rot:]], axis=-1)
    ki = u @ f(p.w_k).T  # (T, Ci): ONE key a token
    mu = jnp.mean(ki, axis=-1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(jnp.mean((ki - mu) ** 2, axis=-1, keepdims=True) + 1e-6) * p.k_norm_w + p.k_norm_b
    ki = jnp.concatenate([_rotate(ki[:, None, :rot], base)[:, 0], ki[:, rot:]], axis=-1)
    w = (u @ f(p.w_w).T) / math.sqrt(Hi * Ci)  # (T, Hi), any sign
    rows = lambda a: jax.lax.dynamic_slice_in_dim(a, r0, n)  # `r0` may be traced (`attention_layer`'s loop)
    I = jnp.einsum("ih,ihj->ij", rows(w), jax.nn.relu(jnp.einsum("ihc,jc->ihj", rows(qi), ki)))
    return I + 0.0  # -0.0 made +0.0: `jax.lax.top_k` orders the two, and equal scores go to the lower position


def attention_layer(p, u, cfg, sliding, f=_f32, select="topk"):
    """u (T, D), the layer's normed input -> (T, D): latent attention in its expanded form."""
    T = u.shape[0]
    H, rq, rkv, dn, dr, dv, base = geometry(cfg, sliding)
    eps, D = cfg["rms_norm_eps"], cfg["n_embd"]
    r_q, r_kv = (math.sqrt(D / rq), math.sqrt(D / rkv)) if cfg["mla_rescale"] else (1.0, 1.0)
    c_q = r_q * _rms(u @ f(p.w_qa).T, p.q_norm, eps)
    q = (c_q @ f(p.w_qb).T).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], base)], axis=-1)
    ckv = u @ f(p.w_kva).T  # (T, rkv + dr)
    kv = ((r_kv * _rms(ckv[:, :rkv], p.kv_norm, eps)) @ f(p.w_kvb).T).reshape(T, H, dn + dv)
    k_r = _rotate(ckv[:, None, rkv:], base)  # (T, 1, dr): one rotated key group, shared by the heads
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (T, H, dr))], axis=-1)
    v = kv[..., dn:]
    j = jnp.arange(T)[None, :]
    n = T if T <= QUERY_BLOCK else math.gcd(T, QUERY_BLOCK)  # equal blocks (the cells pad T to a multiple of 128)

    def block(r0):
        """Rows [r0, r0 + n) against every key. `r0` is TRACED: one compiled body for
        every block, and no block's mask folded into a constant of the program (a
        Python loop over 19 blocks of 4,864 keys made a 32 MB executable)."""
        i = r0 + jnp.arange(n)[:, None]
        keep = j <= i
        if sliding:
            keep = keep & (j > i - cfg["sliding_window"])
        elif select != "dense":
            I = jnp.where(keep, index_scores(p.index, u, c_q, cfg, base, r0, n, f), -jnp.inf)
            _, top = jax.lax.top_k(I, min(cfg["index_topk"], T))
            keep = keep & jnp.zeros((n, T), bool).at[jnp.arange(n)[:, None], top].set(True)
        a = jnp.einsum("ihc,jhc->hij", jax.lax.dynamic_slice_in_dim(q, r0, n), k) / math.sqrt(dn + dr)
        a = jnp.where(keep[None], a, -jnp.inf)
        return jnp.einsum("hij,jhc->ihc", jax.nn.softmax(a, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(0, T, n)).reshape(T, H, dv)
    if p.w_g is not None:
        o = o * jax.nn.sigmoid(u @ f(p.w_g).T)[:, :, None]  # one scalar a head
    return o.reshape(T, H * dv) @ f(p.wo).T


def moe_layer(p, h, cfg, f=_f32, include_shared=True):
    """h (T, D) -> the part of the expert layer's output that the experts held
    (`p.w_gate`'s leading axis, from `expert_offset`) give, plus the shared expert."""
    s = jax.nn.sigmoid(h @ p.router.astype(jnp.float32).T)  # (T, n_experts): the router is never rounded
    _, idx = jax.lax.top_k(s + p.router_bias.astype(jnp.float32), cfg["moe_top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    y = _swiglu(h, p.shared, f) if include_shared else jnp.zeros_like(h)

    def one(e, y):  # the experts held here, each over every token, masked by its pair weight
        w_e = jnp.sum(jnp.where(idx == cfg["expert_offset"] + e, w, 0.0), axis=-1)  # (T,), 0 where e was not selected
        up = jax.nn.silu(h @ f(p.w_gate[e]).T) * (h @ f(p.w_up[e]).T)
        return y + w_e[:, None] * (up @ f(p.w_down[e]).T)

    return jax.lax.fori_loop(0, p.w_gate.shape[0], one, y)


def attention_half(p, x, cfg, i, f=_f32, select="topk"):
    sliding = cfg["layer_types"][i] == "sliding_attention"
    return x + attention_layer(p.attn, _rms(x, p.norm1, cfg["rms_norm_eps"]), cfg, sliding, f, select)


def mlp_half(p, x, cfg, i, f=_f32):
    h = _rms(x, p.norm2, cfg["rms_norm_eps"])
    return x + (_swiglu(h, p.mlp, f) if i < cfg["n_dense_layers"] else moe_layer(p.mlp, h, cfg, f))


def logits(params, tokens, cfg, last=None, rows=None, round_to=None, select="topk"):
    """tokens (T,) int -> float32 logits (T, V), or of the `last` positions
    only, or of the positions `rows` (an int array) only. Two jitted calls a
    layer. `select`: "topk" (the model) or "dense" (the control: no selection)."""
    f = lambda a: _f32(a, round_to)
    jit = lambda name, fn: _jitted((name, repr(sorted(cfg.items())), str(round_to)), fn)
    with jax.default_matmul_precision("highest"):
        x = jit("embed", lambda e, t: jnp.take(f(e), t, axis=0))(params.wte, tokens)
        for i, p in enumerate(params.layers):
            # a half layer is a function of its KIND alone (the index only says which): layers of one kind share
            # a compile, and only a full layer's attention differs under `select`
            sliding, dense = cfg["layer_types"][i] == "sliding_attention", i < cfg["n_dense_layers"]
            x = jit(("attention", sliding, None if sliding else select), lambda p, x, i=i: attention_half(p, x, cfg, i, f, select))(p, x)
            x = jit(("mlp", dense), lambda p, x, i=i: mlp_half(p, x, cfg, i, f))(p, x)
        if last is not None:
            x = x[-last:]
        if rows is not None:
            x = jnp.take(x, jnp.asarray(rows), axis=0)
        return jit("head", lambda w, hw, x: _rms(x, w, cfg["rms_norm_eps"]) @ f(hw).T)(params.final_norm, params.lm_head, x)


_JITS: dict = {}


def _jitted(key, fn):
    """One jitted function a (kind of half layer, configuration, rounding; for a
    full layer's attention also the selection): a check's four sequences, padded
    to one length, and a stack's layers of one kind then share each compile."""
    if key not in _JITS:
        _JITS[key] = jax.jit(fn)
    return _JITS[key]
