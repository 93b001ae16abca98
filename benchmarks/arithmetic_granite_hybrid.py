"""Operations and bytes of Granite-4.0-H's serving step, from shapes. Yardstick
code, kept with the benchmark like arithmetic.py: the counts a roofline share
of this family's kernels and state updates and its weight-read floor are worked
out from, at the PUBLISHED widths and per layer kind, under the function names
arithmetic_olmo_hybrid.py gives (metrics/serve_state_layers.py and
serve_kinds_*.py call them by name). `model` is `dataclasses.asdict` of the
model config as the cell ran it (`layer_types`, `n_layer`, `n_head`,
`n_kv_head`, `n_embd`, `mamba_heads`, `mamba_head_dim`, `mamba_state`,
`mamba_conv`, `mamba_chunk`, `dense_width`, `vocab_size`). Work is what the
equations need at the published shapes: lanes the device's tiling pads (a
64-channel K/V row in 128 lanes), a grid step over a masked block, a SECOND
read of the state where a program sweeps it twice, and the DECAY TILES the
vector unit forms inside a chunk (exp(s_t - s_j): exponentials and elementwise
products, no matrix product) are time spent and no work credited.
"""

from __future__ import annotations

import typing as tp


def layer_kinds(model: dict) -> tp.List[str]:
    """'linear' | 'global' of the layers run (`layer_types` read by index as published)."""
    return ["global" if model["layer_types"][i] == "attention" else "linear" for i in range(model["n_layer"])]


def n_linear(model: dict) -> int:
    return sum(k == "linear" for k in layer_kinds(model))


def n_global(model: dict) -> int:
    return sum(k == "global" for k in layer_kinds(model))


def head_dim(model: dict) -> int:
    return model["n_embd"] // model["n_head"]


def _hpn(model: dict) -> tp.Tuple[int, int, int]:
    return model["mamba_heads"], model["mamba_head_dim"], model["mamba_state"]


def conv_channels(model: dict) -> int:
    """Channels of the short convolution: x (H P) | B (N) | C (N), one group."""
    H, P, N = _hpn(model)
    return H * P + 2 * N


def state_bytes_per_slot(model: dict, history_itemsize: int = 2) -> float:
    """Bytes of ONE slot's state row as published: a float32 (P, N) matrix a
    head of every mamba layer, and the convolution's last mamba_conv - 1
    inputs of its x | B | C channels (76,437,504 B: 36 layers x (2,097,152 +
    26,112))."""
    H, P, N = _hpn(model)
    history = (model["mamba_conv"] - 1) * conv_channels(model) * history_itemsize
    return float(n_linear(model) * (4 * H * P * N + history))


def state_update_token(model: dict, itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the ONE-TOKEN update of an active slot needs over all
    mamba layers: the state read and written once each (2 x 4 x H x P x N); x
    and z in (the stream's dtype, H P each), B and C in (N each, ONE pair for
    all heads), dt in and y out (float32: H and H P); 2 P N multiply-adds a
    head each for the rank-one write dt x B^T and for h C, and P N products for
    the decay: 5 H P N FLOPs a layer."""
    H, P, N = _hpn(model)
    flops = H * (2 * 2 * P * N + P * N)
    bytes_ = 2 * 4 * H * P * N + itemsize * (2 * H * P + 2 * N) + 4 * H + 4 * H * P
    return float(n_linear(model) * flops), float(n_linear(model) * bytes_)


def prefill_scan_token(model: dict, itemsize: int = 2, chunk_tokens: int = 512) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) a PROMPT TOKEN costs in the chunk-carrying scan over
    all mamba layers. FLOPs: the matrix products of the chunked form a chunk of
    Q = `mamba_chunk` tokens (midgpt_tpu/ops/ssd.py's docstring), 2 FLOPs a
    multiply-add: C B^T ONCE for all heads, the causal half of (Q, Q, N): Q^2
    N; a head's (L o C B^T) (dt x), the causal half of (Q, Q, P): Q^2 P; a
    head's C h_0^T (Q, N, P) and its state write (dt x)^T B (P, Q, N): 2 Q P N
    each; over Q tokens: Q N + H (Q P + 4 P N) a token (3,178,496 at Q = 256,
    64 heads of 64 x 128). The decay tiles are not counted (module docstring).
    Bytes: the token's x and z in (the stream's dtype), B and C in, dt in
    (float32, one a head) and y out (float32), and the slot's state read and
    written once a prefill call of `chunk_tokens` tokens, shared by them."""
    H, P, N = _hpn(model)
    Q = model["mamba_chunk"]
    flops = Q * N + H * (Q * P + 4 * P * N)
    bytes_ = itemsize * (2 * H * P + 2 * N) + 4 * H + 4 * H * P + 2 * 4 * H * P * N / chunk_tokens
    return float(n_linear(model) * flops), float(n_linear(model) * bytes_)


def decode_attention_token(model: dict, kind: str, context: int, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the paged decode attention of the attention layers
    needs to produce ONE token whose query attends over `context` cached
    positions: K and V of every position (n_kv_head x head_dim each, read once
    for the n_head / n_kv_head query heads that share them), 2 x context x
    n_head x head_dim multiply-adds for the scores and as many for the values;
    q in and o out. `kind` is 'global' (the one paged kind)."""
    if kind != "global":
        raise KeyError(kind)
    E, Ekv = model["n_head"] * head_dim(model), model["n_kv_head"] * head_dim(model)
    return 4.0 * context * E * n_global(model), float((2 * context * Ekv * kv_itemsize + 2 * E * 2) * n_global(model))


def kv_write_token(model: dict, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) storing ONE token's K and V in every attention layer's pool needs (8,192 B at 8 heads of 64 in 4 layers)."""
    return 0.0, float(2 * model["n_kv_head"] * head_dim(model) * kv_itemsize * n_global(model))


def decode_step_weight_bytes(model: dict, itemsize: int = 2, experts_touched: tp.Optional[float] = None) -> float:
    """Bytes of weights ONE decode step must read, whatever the batch: a mamba
    layer's in_proj (D x (2 H P + 2 N + H)), taps and their bias, and out_proj
    (H P x D); an attention layer's W_q, W_o (D x n_head head_dim each) and
    W_k, W_v (D x n_kv_head head_dim each); every layer's gated MLP (3 x D x
    dense_width); the TIED embedding once, as the head (the rows a step looks
    up are a few KB). The norm gains, A_log, D and dt_bias are left out (a few
    KB a layer). `experts_touched` is taken and ignored: no layer routes."""
    del experts_touched
    D, (H, P, N) = model["n_embd"], _hpn(model)
    E, Ekv = model["n_head"] * head_dim(model), model["n_kv_head"] * head_dim(model)
    mamba = D * (2 * H * P + 2 * N + H) + conv_channels(model) * (model["mamba_conv"] + 1) + H * P * D
    total = n_linear(model) * mamba + n_global(model) * 2 * D * (E + Ekv) + model["n_layer"] * 3 * D * model["dense_width"]
    return float(itemsize * (total + model["vocab_size"] * D))
