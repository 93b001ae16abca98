"""Operations and bytes of a LOOPED model's serving step (Ouro: `n_layer`
layers of weights applied `n_loop` times, a paged cache of `n_loop * n_layer`
layers), from shapes. Yardstick code, kept with the benchmark like
arithmetic.py: the counts a roofline share of this family's kernels and its
weight-read floor are worked out from, at the PUBLISHED widths. `model` is
`dataclasses.asdict` of the model config as the cell ran it (`n_loop`,
`n_layer`, `n_head`, `head_dim`, `n_embd`, `dense_width`, `vocab_size`).
`arithmetic.paged_attention_token` multiplies by `n_layer`; here a token's
attention sweeps, and its write fills, `n_loop` times as many cache layers.
"""

from __future__ import annotations

import typing as tp


def cache_layers(model: dict) -> int:
    """Layers of the paged cache: every pass of every layer keeps its own keys and values."""
    return model["n_loop"] * model["n_layer"]


def decode_attention_token(model: dict, context: int, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) paged decode attention needs to produce ONE token
    whose query attends over `context` cached positions, all `n_loop *
    n_layer` cache layers: K and V of every position read once (n_head x
    head_dim each), 2 x context x n_head x head_dim multiply-adds for q K^T
    and as many for p V."""
    E = model["n_head"] * model["head_dim"]
    return 4.0 * context * E * cache_layers(model), float(2 * E * kv_itemsize * context * cache_layers(model))


def kv_write_token(model: dict, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) storing ONE token in every cache layer needs: its K
    and its V row (n_head x head_dim each), written once."""
    return 0.0, float(2 * model["n_head"] * model["head_dim"] * kv_itemsize * cache_layers(model))


def decode_step_weight_bytes(model: dict, itemsize: int = 2) -> float:
    """Bytes of weights ONE decode step must read, whatever the batch: the
    layers' matrices once a PASS (q, k, v, o: 4 x D x n_head x head_dim; gate,
    up, down: 3 x D x dense_width), and the head once. The embedding's rows, the
    norm gains and the gate's row are left out (a few KB a token)."""
    D = model["n_embd"]
    layer = 4 * D * model["n_head"] * model["head_dim"] + 3 * D * model["dense_width"]
    return float(itemsize * (model["n_loop"] * model["n_layer"] * layer + model["vocab_size"] * D))
