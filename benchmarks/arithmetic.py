"""Operations and bytes from shapes, and the table of peaks. Yardstick code:
kept with the benchmark so that no PR that claims a gain can change how a
utilization or a roofline share is worked out.

`flops_per_token` is a copy of `midgpt_tpu/training/metrics.py`
(flops_per_token, dense path): 6N for the matmuls plus the 12*L*D*T
attention-scores term, the tied embedding counted once. Recomputed
operations (remat) do not count.
"""

from __future__ import annotations

import json
import os
import typing as tp

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown device_kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmarks/peaks.json; a "
            "utilization against an assumed peak is not a measurement"
        )
    return table[device_kind]


def flops_per_token(model: dict, seq_len: tp.Optional[int] = None) -> float:
    """Training FLOPs per token (forward + backward) of a dense GPT."""
    T = seq_len or model["block_size"]
    D, L, V = model["n_embd"], model["n_layer"], model["vocab_size"]
    head_dim = D // model["n_head"]
    n_params = V * D + L * (4 * D * D + 8 * D * D + 2 * head_dim)
    return 6.0 * n_params + 12.0 * L * D * T


def flash_attention_step(model: dict, n_sequences: int) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the flash-attention kernels need for one optimizer
    step over `n_sequences` sequences of block_size tokens, all layers.

    Forward: QK^T and PV, 2*T*T*D multiply-adds each -> 4*T^2*D FLOPs, halved
    by the causal mask. Backward: five such products (recomputed scores, dV,
    dP, dQ, dK) = 2.5 x forward; the recomputation is the algorithm's own
    (FlashAttention's accounting), so it counts here though not in MFU.
    Bytes, bf16: forward reads q, k, v and writes o (+ f32 logsumexp);
    backward reads q, k, v, o, do, lse and writes dq, dk, dv."""
    T, D, L, H = model["block_size"], model["n_embd"], model["n_layer"], model["n_head"]
    fwd = 4.0 * T * T * D * 0.5
    flops = 3.5 * fwd * n_sequences * L
    act = T * D * 2  # one (T, D) bf16 activation
    lse = T * H * 4
    bytes_ = ((4 * act + lse) + (5 * act + lse + 3 * act)) * n_sequences * L
    return flops, float(bytes_)


def paged_attention_token(model: dict, context: int, kv_itemsize: int = 2) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) paged decode attention needs to produce ONE token
    whose query attends over `context` cached positions, all layers: it reads
    K and V of every position once (n_kv_heads * head_dim each) and does
    2*context*D multiply-adds for QK^T and as many for PV."""
    D, L, H = model["n_embd"], model["n_layer"], model["n_head"]
    kv_heads = model.get("n_kv_heads") or H
    head_dim = D // H
    bytes_ = 2 * kv_heads * head_dim * kv_itemsize * context * L + 2 * D * 2 * L
    flops = 4.0 * context * D * L
    return flops, float(bytes_)


def roofline_share(flops: float, bytes_: float, seconds: float, peaks: dict, chips: int = 1) -> tp.Tuple[float, str]:
    """(percent of roofline, which bound binds). The least time the chips
    could take is the larger of flops/peak FLOP/s and bytes/peak bytes/s."""
    t_c = flops / (peaks["bf16_flops_per_s"] * chips)
    t_m = bytes_ / (peaks["hbm_bytes_per_s"] * chips)
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
