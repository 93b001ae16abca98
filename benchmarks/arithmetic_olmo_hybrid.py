"""Operations and bytes of Olmo-Hybrid's serving step, from shapes. Yardstick
code, kept with the benchmark like arithmetic.py: the counts a roofline share
of this family's kernels and state updates and its weight-read floor are worked
out from, at the PUBLISHED widths and per layer kind. `model` is
`dataclasses.asdict` of the model config as the cell ran it (`layer_types`,
`n_layer`, `n_head`, `n_embd`, `linear_heads`, `linear_key_dim`,
`linear_value_dim`, `conv_kernel`, `dense_width`, `vocab_size`). Work is what
the equations need at the published shapes: lanes the device's tiling pads (a
192-wide row in 256 lanes), a grid step over a masked block, and the DECAY
TILES the vector unit forms inside a chunk (e^{G_r - G_i}: exponentials and
elementwise products, no matrix product) are time spent and no work credited.
"""

from __future__ import annotations

import typing as tp

CHUNK = 64  # tokens a chunk of the chunked delta rule (midgpt_tpu/ops/kda.py CHUNK)


def layer_kinds(model: dict) -> tp.List[str]:
    """'linear' | 'global' of the layers run (`layer_types` read by index as published)."""
    return ["global" if model["layer_types"][i] == "full_attention" else "linear" for i in range(model["n_layer"])]


def n_linear(model: dict) -> int:
    return sum(k == "linear" for k in layer_kinds(model))


def n_global(model: dict) -> int:
    return sum(k == "global" for k in layer_kinds(model))


def head_dim(model: dict) -> int:
    return model["n_embd"] // model["n_head"]


def state_bytes_per_slot(model: dict, history_itemsize: int = 2) -> float:
    """Bytes of ONE slot's state row as published: a float32 (d_k, d_v) matrix a
    head of every linear layer, and the convolution's last conv_kernel - 1
    inputs of its q | k | v channels (27,371,520 B at the cut: 12 layers x
    (2,211,840 + 69,120))."""
    H, dk, dv = model["linear_heads"], model["linear_key_dim"], model["linear_value_dim"]
    history = (model["conv_kernel"] - 1) * H * (2 * dk + dv) * history_itemsize
    return float(n_linear(model) * (4 * H * dk * dv + history))


def state_update_token(model: dict, itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the ONE-TOKEN delta-rule update of an active slot
    needs over all linear layers: the state read and written once each (2 x 4 x
    H x d_k x d_v), its q, k, v in and o out (the stream's dtype), g and beta
    (float32, one a head); 2 d_k d_v multiply-adds a head each for S'^T k, the
    rank-one write and S^T q, and d_k d_v products for the decay."""
    H, dk, dv = model["linear_heads"], model["linear_key_dim"], model["linear_value_dim"]
    flops = H * (3 * 2 * dk * dv + dk * dv)
    bytes_ = 2 * 4 * H * dk * dv + itemsize * H * (2 * dk + dv) + 4 * H * dv + 2 * 4 * H
    return float(n_linear(model) * flops), float(n_linear(model) * bytes_)


def prefill_scan_token(model: dict, itemsize: int = 2, chunk_tokens: int = 512) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) a PROMPT TOKEN costs in the chunk-carrying scan over
    all linear layers. FLOPs: the matrix products of the chunked form a chunk of
    C = 64 tokens a head (midgpt_tpu/ops/kda.py's docstring), 2 FLOPs a
    multiply-add: A and B, causal halves of (C, C, d_k) each: 2 C^2 d_k; the
    block solve's forward substitution over [V | K e^G]: C^2 (d_v + d_k); the
    three products with the state (W_k S_0, Q S_0, K^T U): 6 C d_k d_v; tril(B)
    U: C^2 d_v; over C tokens: C (3 d_k + 2 d_v) + 6 d_k d_v a token a head
    (153,600 at 96 x 192). The decay tiles are not counted (module docstring),
    nor the diagonal blocks' 16 x 16 inverses. Bytes: the token's q, k, v in and
    o out (the stream's dtype), its g and beta (float32, one scalar a head as
    published), and the slot's state read and written once a prefill call of
    `chunk_tokens` tokens, shared by them."""
    H, dk, dv, C = model["linear_heads"], model["linear_key_dim"], model["linear_value_dim"], CHUNK
    flops = H * (C * (3 * dk + 2 * dv) + 6 * dk * dv)
    bytes_ = itemsize * H * (2 * dk + 2 * dv) + 2 * 4 * H + 2 * 4 * H * dk * dv / chunk_tokens
    return float(n_linear(model) * flops), float(n_linear(model) * bytes_)


def decode_attention_token(model: dict, kind: str, context: int, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the paged decode attention of the full layers needs to
    produce ONE token whose query attends over `context` cached positions: K and
    V of every position (n_head x head_dim each: multi-head, no grouping), 2 x
    context x n_head x head_dim multiply-adds for the scores and as many for
    the values; q in and o out. `kind` is 'global' (the one paged kind)."""
    if kind != "global":
        raise KeyError(kind)
    E = model["n_head"] * head_dim(model)
    return 4.0 * context * E * n_global(model), float((2 * context * E * kv_itemsize + 2 * E * 2) * n_global(model))


def kv_write_token(model: dict, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) storing ONE token's K and V in every full layer's pool needs."""
    return 0.0, float(2 * model["n_head"] * head_dim(model) * kv_itemsize * n_global(model))


def decode_step_weight_bytes(model: dict, itemsize: int = 2, experts_touched: tp.Optional[float] = None) -> float:
    """Bytes of weights ONE decode step must read, whatever the batch: a linear
    layer's W_q, W_k (D x H d_k each), W_v, W_g, W_o (D x H d_v each), W_b, W_a
    (D x H each) and taps; a full layer's four D x D projections; every layer's
    SwiGLU (3 x D x dense_width); the head once. The embedding's rows, the norm
    gains, A_log and dt_bias are left out (a few KB a token). `experts_touched`
    is taken and ignored: no layer routes."""
    del experts_touched
    D, H, dk, dv = model["n_embd"], model["linear_heads"], model["linear_key_dim"], model["linear_value_dim"]
    linear = D * H * (2 * dk + 3 * dv) + 2 * D * H + H * (2 * dk + dv) * model["conv_kernel"]
    total = n_linear(model) * linear + n_global(model) * 4 * D * D + model["n_layer"] * 3 * D * model["dense_width"]
    return float(itemsize * (total + model["vocab_size"] * D))
