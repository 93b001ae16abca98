"""Operations and bytes of openPangu-Ultra-MoE's latent (MLA) serving kernels,
from shapes. Yardstick code, kept with the benchmark like arithmetic.py: the
counts a roofline share of this family's kernels is worked out from, at the
PUBLISHED widths (kv_lora_rank 512, qk_rope 64, 128 heads). `model` is
`dataclasses.asdict` of the model config as the cell ran it. A channel the
program pads to reach a lane width (576 -> 640), a row it reads twice, and a
key it reads beyond what the mask lets through, is time it spends and no work
it is credited with.
"""

from __future__ import annotations

import typing as tp


def latent_dim(model: dict) -> int:
    """What a token keeps in a layer's cache: the normed latent and the rotated shared key."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def latent_decode_attention_token(model: dict, context: int, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the ABSORBED decode attention needs to produce ONE
    token whose query attends over `context` cached positions, all layers:
    every head's folded query (kv_lora_rank + rope channels) scores every
    cached row and the probabilities weigh its kv_lora_rank latent channels, 2
    x n_head x context x (576 + 512) a layer; the latent rows are read ONCE
    (context x 576 values a layer), whatever the number of heads."""
    r, d = model["kv_lora_rank"], latent_dim(model)
    L, H = model["n_layer"], model["n_head"]
    return 2.0 * H * context * (d + r) * L, float(context * d * kv_itemsize * L)


def kv_write_token(model: dict, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) storing ONE token in every layer's pool needs: the
    576 values of its row, written once."""
    return 0.0, float(latent_dim(model) * kv_itemsize * model["n_layer"])
