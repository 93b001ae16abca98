"""MiMo-V2.5 (the language model of MiMo-V2-Flash 309B-A15B) as published: 48
layers (9 global : 39 sliding-window of 128, a global layer first and then one
in six), hidden 4,096, 64 query heads of 192 over 4 (global) or 8 (window) K/V
heads, value heads of 128, rotary on 64 of the 192 channels with base 1e7
(global) or 1e4 (window), a learned sink bias in the window layers, one dense
SwiGLU layer of 16,384 then 256 routed experts of 2,048 (top-8, sigmoid router,
renormalised, no shared expert), vocabulary 152,576, untied head.

Source: https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json

This preset is the PUBLISHED configuration whole (309 B parameters), which no
machine this repo runs on holds. It exists so that the widths are written
once; what runs is a cut of it (fewer layers, the experts and vocabulary rows
one chip of an expert-parallel deployment holds), and the cut lives with
whoever makes it: `benchmarks/configs/mimo_v2_5_ep16.json` (`overrides`), or
`--set model_config.n_layer=7 ...`. The family is SERVED (sample.py
--engine=continuous, ServeEngine); `launch.py` refuses it by name
(models/mimo_v2.py `check_training`), so the optimizer fields below are the
Kimi preset's and mean nothing here. `block_size` 32,768 is this repo's
serving cap (the source declares 1,048,576 positions). Left out: the three
multi-token-prediction layers and the vision and audio towers.
"""

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.mimo_v2 import MimoV2Config

config = ExperimentConfig(
    rundir="",
    data_dir="data/local_text",
    learning_rate=3e-4,
    batch_size=1,
    warmup_steps=2000,
    min_lr=3e-5,
    lr_decay_steps=100000,
    max_steps=100000,
    beta2=0.95,
    weight_decay=1e-4,
    eval_interval=1000,
    g_accum_iters=1,
    compute_dtype="bfloat16",
    param_dtype="float32",
    shard_model=False,
    mesh=MeshConfig(data=-1, fsdp=1, sp=1),
    model_config=MimoV2Config(
        block_size=32768,
        vocab_size=152576,
        n_layer=48,
        n_head=64,
        n_embd=4096,
        layer_pattern=(0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1) * 7 + (0,),
        moe_layer_freq=(0,) + (1,) * 47,
        head_dim=192,
        v_head_dim=128,
        n_kv_heads=4,
        swa_head_dim=192,
        swa_v_head_dim=128,
        swa_n_kv_heads=8,
        partial_rotary_factor=0.334,
        rope_theta=1e7,
        swa_rope_theta=1e4,
        sliding_window=128,
        attention_value_scale=0.707,
        swa_sink_bias=True,
        full_sink_bias=False,
        dense_width=16384,
        n_experts=256,
        n_experts_held=256,
        expert_offset=0,
        moe_top_k=8,
        expert_width=2048,
        routed_scaling_factor=1.0,
        rms_norm_eps=1e-5,
    ),
)
