"""Kimi-Linear-48B-A3B as published: 27 layers (20 KDA : 7 MLA, 3:1), hidden
2,304, 32 heads, one dense SwiGLU layer of 9,216 then 256 routed experts of
1,024 (top-8, sigmoid router, renormalised, x2.446) plus one shared expert,
vocabulary 163,840, untied head.

Source: https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json

This preset is the PUBLISHED configuration whole: 49.1 B parameters, 786 GB of
training state at 16 B a parameter, which no machine this repo runs on holds.
It exists so that the widths are written once; what runs is a cut of it
(fewer layers, the experts and vocabulary rows one chip of an expert-parallel
job holds), and the cut lives with whoever makes it: the benchmark's
configuration file `benchmarks/configs/kimi_linear_48b_a3b_ep32.json`
(`overrides`), or `--set model_config.n_layer=5 ...` on the command line.
bf16 compute over f32 weights, like every preset. The source publishes no
optimizer constants: the chain is the repo's (clip 1.0, AdamW beta2 0.95,
decoupled wd, warmup + cosine), with a peak rate and a warm-up a model of this
size can take (3e-4 over 2,000 steps; the 124M's 1e-3 over 300 steps sends
every token to the same experts within fifty steps when no balancing rule
moves the router's bias, PERF.md §6 PR 26).
The training sequence length (8,192) is assumed: config.json gives only
`model_max_length` (1,048,576).
"""

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.kimi_linear import KimiLinearConfig

config = ExperimentConfig(
    rundir="",
    data_dir="data/local_text",
    learning_rate=3e-4,
    batch_size=2,
    warmup_steps=2000,
    min_lr=3e-5,
    lr_decay_steps=100000,
    max_steps=100000,
    beta2=0.95,
    weight_decay=1e-4,
    eval_interval=1000,
    eval_steps=20,
    compute_dtype="bfloat16",
    param_dtype="float32",
    g_accum_iters=2,
    shard_model=False,
    mesh=MeshConfig(data=-1, fsdp=1, sp=1),
    model_config=KimiLinearConfig(
        block_size=8192,
        vocab_size=163840,
        n_layer=27,
        n_head=32,
        n_embd=2304,
        kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26),
        full_attn_layers=(4, 8, 12, 16, 20, 24, 27),
        kda_head_dim=128,
        kda_conv_size=4,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        dense_width=9216,
        first_k_dense=1,
        n_experts=256,
        n_experts_held=256,
        expert_offset=0,
        moe_top_k=8,
        expert_width=1024,
        n_shared_experts=1,
        routed_scaling_factor=2.446,
        rms_norm_eps=1e-5,
        attn_impl="flash",
    ),
)
