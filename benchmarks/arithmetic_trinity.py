"""Operations and bytes of Trinity's (AFMoE's) serving step, from shapes.
Yardstick code, kept with the benchmark like arithmetic.py: the counts a
roofline share of this family's kernels, its weight-read floor and its expert
loop's read floor are worked out from, at the PUBLISHED widths and per layer
kind. `model` is `dataclasses.asdict` of the model config as the cell ran it
(`layer_types`, `n_layer`, `n_dense_layers`, `n_head`, `n_kv_heads`,
`head_dim`, `sliding_window`, `n_embd`, `dense_width`, `expert_width`,
`n_experts`, `n_experts_held`, `n_shared_experts`, `vocab_size`). A key the
program reads beyond what the mask lets through, and a block its grid steps
over, is time it spends and no work it is credited with.
"""

from __future__ import annotations

import typing as tp


def layer_kinds(model: dict) -> tp.List[str]:
    """'global' | 'window' of the layers run (`layer_types` read by index as published)."""
    return ["window" if model["layer_types"][i] == "sliding_attention" else "global" for i in range(model["n_layer"])]


def decode_attention_token(model: dict, kind: str, context: int, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the decode attention of the layers of `kind` needs to
    produce ONE token whose query attends over `context` cached positions: a
    global layer reads K and V (n_kv_heads x head_dim each) of every position,
    a window layer of the last `sliding_window` at most; 2 x keys x n_head x
    head_dim multiply-adds for the scores and as many for the values (16,384
    FLOPs and 2,048 B a key a layer at the published widths); q in and o out."""
    n = sum(k == kind for k in layer_kinds(model))
    keys = min(context, model["sliding_window"]) if kind == "window" else context
    E, E_kv = model["n_head"] * model["head_dim"], model["n_kv_heads"] * model["head_dim"]
    return 4.0 * keys * E * n, float((2 * keys * E_kv * kv_itemsize + 2 * E * 2) * n)


def kv_write_token(model: dict, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) storing ONE token's K and V in every layer's pool
    needs: the rows themselves, n_kv_heads x head_dim each, written once a layer."""
    return 0.0, float(2 * model["n_kv_heads"] * model["head_dim"] * kv_itemsize * model["n_layer"])


def expert_bytes(model: dict, itemsize: int = 2) -> float:
    """Bytes of ONE routed expert's three matrices (12,582,912 at the published widths in bf16)."""
    return float(3 * model["n_embd"] * model["expert_width"] * itemsize)


def decode_step_weight_bytes(model: dict, itemsize: int = 2, experts_touched: tp.Optional[float] = None) -> float:
    """Bytes of weights ONE decode step must read: every layer's attention
    matrices (q, gate, o: 3 x D x n_head x head_dim; k, v: 2 x D x n_kv_heads x
    head_dim), a dense layer's SwiGLU, a routed layer's router, shared expert
    and the `experts_touched` held experts a layer that some slot's pair
    selects (None: every held expert, the floor of a step that touches all),
    and the head once. The embedding's rows and the norm gains are left out
    (a few KB a token)."""
    D = model["n_embd"]
    attn = D * model["head_dim"] * (3 * model["n_head"] + 2 * model["n_kv_heads"])
    n_moe = model["n_layer"] - model["n_dense_layers"]
    touched = model["n_experts_held"] if experts_touched is None else experts_touched
    moe = model["n_experts"] * D + 3 * D * model["expert_width"] * (model["n_shared_experts"] + touched)
    total = model["n_layer"] * attn + model["n_dense_layers"] * 3 * D * model["dense_width"] + n_moe * moe
    return float(itemsize * (total + model["vocab_size"] * D))
