"""Operations and bytes of dots3-note's serving step, from shapes. Yardstick
code, kept with the benchmark like arithmetic.py: the counts a roofline share
of this family's decode attention (the window layers' kernel; the full layers'
index sweep and selected attention, whatever implements them), its weight-read
floor and its expert read floor are worked out from, at the PUBLISHED widths and
per layer kind. `model` is `dataclasses.asdict` of the model config as the cell
ran it (models/dots3.py `Dots3Config`). A lane the program pads to (576 -> 640,
1,088 -> 1,152), a row it reads twice or gathers into a copy, a key it reads
beyond what the mask or the selection lets through, and a grid step over a
block behind the window, is time it spends and no work it is credited with.
"""

from __future__ import annotations

import typing as tp


def layer_kinds(model: dict) -> tp.List[str]:
    """'latent' | 'window_latent' of the layers run."""
    return ["window_latent" if t == "sliding_attention" else "latent" for t in model["layer_types"][:model["n_layer"]]]


def _n(model: dict, kind: str) -> int:
    return sum(k == kind for k in layer_kinds(model))


def decode_attention_token(model: dict, kind: str, context: int, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the ABSORBED decode attention of the layers of `kind`
    needs to produce ONE token whose query attends over `context` cached
    positions. 'window_latent': the last min(context, sliding_window) rows of
    swa_kv_lora_rank + rope values, read once whatever the heads; every head's
    folded query scores a row's 1,088 channels and weighs its 1,024: 2 x 64 x
    (1,088 + 1,024) FLOPs a key a layer. 'latent': the rows the indexer
    selected, `select_attention_token`."""
    if kind != "window_latent":
        return select_attention_token(model, context, kv_itemsize)
    r, d = model["swa_kv_lora_rank"], model["swa_kv_lora_rank"] + model["swa_qk_rope_head_dim"]
    keys, H, n = min(context, model["sliding_window"]), model["swa_n_head"], _n(model, kind)
    return 2.0 * H * keys * (d + r) * n, float((keys * d * kv_itemsize + H * (d + r) * 2) * n)


def index_sweep_token(model: dict, context: int, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the indexer's sweep needs for ONE decoded token over
    `context` cached index keys, all full layers: each key's index_head_dim
    values read once, scored by every index head (2 x 64 x 128 = 16,384 FLOPs a
    key a layer; the ReLU and the heads' weighted sum ride free)."""
    Hi, Ci, n = model["index_n_heads"], model["index_head_dim"], _n(model, "latent")
    return 2.0 * Hi * Ci * context * n, float(context * Ci * kv_itemsize * n)


def select_attention_token(model: dict, context: int, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the absorbed attention over the SELECTED rows needs
    for ONE decoded token, all full layers: min(context, index_topk) rows of
    kv_lora_rank + rope values read once; every head's folded query scores a
    row's 576 channels and weighs its 512 (2 x 128 x 1,088 = 278,528 FLOPs a
    row a layer)."""
    r, d = model["kv_lora_rank"], model["kv_lora_rank"] + model["qk_rope_head_dim"]
    rows, H, n = min(context, model["index_topk"]), model["n_head"], _n(model, "latent")
    return 2.0 * H * rows * (d + r) * n, float((rows * d * kv_itemsize + H * (d + r) * 2) * n)


def kv_write_token(model: dict, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) storing ONE token in every pool array needs: a full
    layer's latent row (576 values) and index key (128), a sliding layer's
    latent row (1,088), each written once."""
    full = model["kv_lora_rank"] + model["qk_rope_head_dim"] + model["index_head_dim"]
    window = model["swa_kv_lora_rank"] + model["swa_qk_rope_head_dim"]
    return 0.0, float((full * _n(model, "latent") + window * _n(model, "window_latent")) * kv_itemsize)


def expert_bytes(model: dict, itemsize: int = 2) -> float:
    """Bytes of ONE routed expert's three matrices (47,185,920 at the published widths in bf16)."""
    return float(3 * model["n_embd"] * model["expert_width"] * itemsize)


def attention_weights(model: dict, kind: str) -> int:
    """Parameters of one attention layer of `kind` that a step reads (norm gains left out)."""
    D = model["n_embd"]
    if kind == "latent":
        H, rq, rkv = model["n_head"], model["q_lora_rank"], model["kv_lora_rank"]
        dn, dr, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
        index = rq * model["index_n_heads"] * model["index_head_dim"] + D * (model["index_head_dim"] + model["index_n_heads"])
    else:
        H, rq, rkv = model["swa_n_head"], model["swa_q_lora_rank"], model["swa_kv_lora_rank"]
        dn, dr, dv = model["swa_qk_nope_head_dim"], model["swa_qk_rope_head_dim"], model["swa_v_head_dim"]
        index = 0
    gate = H * D if model["headwise_gate"] else 0
    return D * (rq + rkv + dr) + rq * H * (dn + dr) + rkv * H * (dn + dv) + H * dv * D + gate + index


def decode_step_weight_bytes(model: dict, itemsize: int = 2, experts_touched: tp.Optional[float] = None) -> float:
    """Bytes of weights ONE decode step must read: every layer's attention
    matrices of its kind (the indexer's with a full layer's), a dense layer's
    SwiGLU, a routed layer's router, shared expert and the `experts_touched`
    held experts a layer that some slot's pair selects (None: every held
    expert), and the head once. The embedding's rows and the norm gains are
    left out (a few KB a token)."""
    D = model["n_embd"]
    n_moe = model["n_layer"] - model["n_dense_layers"]
    touched = model["n_experts_held"] if experts_touched is None else experts_touched
    moe = model["n_experts"] * D + 3 * D * model["expert_width"] * (model["n_shared_experts"] + touched)
    total = sum(attention_weights(model, k) for k in layer_kinds(model))
    total += model["n_dense_layers"] * 3 * D * model["dense_width"] + n_moe * moe + model["vocab_size"] * D
    return float(itemsize * total)
