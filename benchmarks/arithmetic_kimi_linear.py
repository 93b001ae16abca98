"""Operations and bytes of the Kimi-Linear step, from shapes. Yardstick code,
kept with the benchmark like arithmetic.py: the counts a utilization or a
roofline share of this family is worked out from. `model` is
`dataclasses.asdict` of the model config as the cell ran it.

`flops_per_token` is the yardstick's own copy of `midgpt_tpu/models/
kimi_linear.py` (KimiLinear.flops_per_token, which the train loop's MFU line
uses; as arithmetic.py copies the GPT's): what is computed HERE, forward +
backward = 3 x forward, recomputed operations (remat, the flash backward's own
recomputation) not counted. tests/test_kimi_linear.py holds the two equal.
"""

from __future__ import annotations

import typing as tp


def layer_kinds(model: dict) -> tp.List[tp.Tuple[str, str]]:
    """[(mixer, mlp)] of the layers run: 'kda' | 'mla', 'dense' | 'moe'."""
    out = []
    for i in range(model["n_layer"]):
        mixer = "kda" if i + 1 in model["kda_layers"] else "mla"
        out.append((mixer, "dense" if i < model["first_k_dense"] else "moe"))
    return out


def flops_per_token(model: dict, assignments_here: tp.Optional[float] = None) -> float:
    """Training FLOPs a token: 6 x the parameters a token multiplies (a routed
    expert once per token-expert pair routed to an expert held here:
    `assignments_here` pairs a token summed over the MoE layers; default the
    balanced share top_k * held / n_experts a layer), plus 3 x the forward
    FLOPs of MLA's causal scores and values (192 and 128 channels) and of the
    KDA recurrence (per token and head three products of d_k x d_v)."""
    T, D, H, V = model["block_size"], model["n_embd"], model["n_head"], model["vocab_size"]
    d, r = model["kda_head_dim"], model["kda_gate_rank"]
    dq, dv, lora = model["qk_nope_head_dim"] + model["qk_rope_head_dim"], model["v_head_dim"], model["kv_lora_rank"]
    kda = 3 * H * d * D + D * H * d + 2 * (r * D + H * d * r) + H * D
    mla = H * dq * D + (lora + model["qk_rope_head_dim"]) * D + H * (model["qk_nope_head_dim"] + dv) * lora + D * H * dv
    expert = 3 * D * model["expert_width"]
    kinds = layer_kinds(model)
    if assignments_here is None:
        assignments_here = (sum(m == "moe" for _, m in kinds) * model["moe_top_k"]
                            * model["n_experts_held"] / model["n_experts"])
    matmul = V * D + assignments_here * expert
    other = 0.0
    for mixer, mlp in kinds:
        matmul += kda if mixer == "kda" else mla
        other += 3 * 2 * H * d * d if mixer == "kda" else 2 * H * (dq + dv) * T / 2
        matmul += 3 * D * model["dense_width"] if mlp == "dense" else (
            model["n_experts"] * D + model["n_shared_experts"] * expert)
    return 6.0 * matmul + 3.0 * other


def mla_attention_step(model: dict, n_sequences: int) -> tp.Tuple[float, float]:
    """(FLOPs, HBM bytes) the attention of the MLA layers needs for one
    optimizer step over `n_sequences` sequences, at the PUBLISHED head widths
    (q/k 192, v 128), whatever width the kernel is handed (the program pads to
    256: padded channels are no work the algorithm needs).

    Forward: QK^T 2*T*T*192 and PV 2*T*T*128 a head, halved by the causal mask.
    Backward: recomputed scores and dQ, dK at 192, dP and dV at 128: 3 x 192 +
    2 x 128 against the forward's 192 + 128; the recomputation is the
    algorithm's own (FlashAttention's accounting, as arithmetic.py counts it).
    Bytes, bf16: forward reads q, k, v and writes o (+ f32 logsumexp); backward
    reads q, k, v, o, do, lse and writes dq, dk, dv."""
    T, H = model["block_size"], model["n_head"]
    dq, dv = model["qk_nope_head_dim"] + model["qk_rope_head_dim"], model["v_head_dim"]
    n_mla = sum(mixer == "mla" for mixer, _ in layer_kinds(model))
    pair = 2.0 * T * T * 0.5 * H  # one T x T product a channel, causal, all heads
    flops = pair * ((dq + dv) + (3 * dq + 2 * dv)) * n_sequences * n_mla
    qk, vo, lse = T * H * dq * 2, T * H * dv * 2, T * H * 4
    bytes_ = ((2 * qk + 2 * vo + lse) + (2 * qk + 3 * vo + lse + 2 * qk + vo)) * n_sequences * n_mla
    return flops, float(bytes_)
