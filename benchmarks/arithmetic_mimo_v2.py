"""Operations and bytes of MiMo-V2's paged attention, from shapes. Yardstick
code, kept with the benchmark like arithmetic.py: the counts a roofline share
of this family's kernels is worked out from, at the PUBLISHED head widths (q/k
192, v 128) and per layer kind. `model` is `dataclasses.asdict` of the model
config as the cell ran it. A channel the program pads to reach a lane width,
and a key it reads beyond what the mask lets through, is time it spends and no
work it is credited with.
"""

from __future__ import annotations

import typing as tp


def layer_kinds(model: dict) -> tp.List[str]:
    """'global' | 'window' of the layers run."""
    return ["window" if model["layer_pattern"][i] else "global" for i in range(model["n_layer"])]


def _geometry(model: dict, kind: str) -> tp.Tuple[int, int, int]:
    if kind == "window":
        return model["swa_n_kv_heads"], model["swa_head_dim"], model["swa_v_head_dim"]
    return model["n_kv_heads"], model["head_dim"], model["v_head_dim"]


def decode_attention_token(model: dict, kind: str, context: int, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the decode attention of the layers of `kind` needs to
    produce ONE token whose query attends over `context` cached positions: a
    global layer reads K (n_kv x 192) and V (n_kv x 128) of every position, a
    window layer of the last `sliding_window` at most; 2 x keys x n_head x 192
    multiply-adds for the scores and x 128 for the values; q in and o out."""
    n_kv, dq, dv = _geometry(model, kind)
    n = sum(k == kind for k in layer_kinds(model))
    keys = min(context, model["sliding_window"]) if kind == "window" else context
    H = model["n_head"]
    flops = 2.0 * keys * H * (dq + dv) * n
    bytes_ = (keys * n_kv * (dq + dv) * kv_itemsize + H * (dq + dv) * 2) * n
    return flops, float(bytes_)


def kv_write_token(model: dict, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) storing ONE token's K and V in every layer's pool
    needs: the rows themselves, n_kv x (192 + 128) a layer, written once."""
    bytes_ = sum((lambda g: g[0] * (g[1] + g[2]))(_geometry(model, k)) for k in layer_kinds(model)) * kv_itemsize
    return 0.0, float(bytes_)
