"""openPangu-Ultra-MoE-718B as published: 61 layers, hidden 7,680, 128 heads of
latent attention (q_lora_rank 1,536, kv_lora_rank 512, qk_nope 128 + qk_rope 64
with ONE rotated key group shared by the heads, v 128, rotary base 25.6e6),
sandwich norms (four RMSNorms a layer), a dense SwiGLU of 18,432 in the first 3
layers, then 256 routed experts of 2,048 (top-8, sigmoid router renormalised and
scaled by 2.5) beside one shared expert, vocabulary 153,600, untied head.

Source: https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json

This preset is the PUBLISHED configuration whole (718 B parameters), which no
machine this repo runs on holds. It exists so that the widths are written
once; what runs is a cut of it (fewer layers, the experts and vocabulary rows
one chip of an expert-parallel deployment holds), and the cut lives with
whoever makes it: `benchmarks/configs/openpangu_ultra_moe_ep16.json`
(`overrides`), or `--set model_config.n_layer=5 ...`. The family is SERVED
(sample.py --engine=continuous, ServeEngine) from a latent paged cache;
`launch.py` refuses it by name (models/pangu_ultra.py `check_training`), so
the optimizer fields below are the Kimi preset's and mean nothing here.
`block_size` 65,536 is this repo's serving cap on prompt + output (the source
declares 131,072 positions). Left out: the next-token-prediction layer.
"""

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.pangu_ultra import PanguUltraConfig

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
    model_config=PanguUltraConfig(
        block_size=65536,
        vocab_size=153600,
        n_layer=61,
        n_head=128,
        n_embd=7680,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=25.6e6,
        dense_width=18432,
        first_k_dense=3,
        n_experts=256,
        n_experts_held=256,
        expert_offset=0,
        moe_top_k=8,
        expert_width=2048,
        n_shared_experts=1,
        routed_scaling_factor=2.5,
        moe_renormalize=True,
        sandwich_norm=True,
        rms_norm_eps=1e-5,
    ),
)
