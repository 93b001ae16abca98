"""dots3-note-prev as published (`model_type: dots3_note`, 288B-A17B): 46 layers,
hidden 5,120; 13 `full_attention` layers of latent attention (128 heads,
q_lora_rank 1,024, kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128, rotary
base 8e7) whose indexer (64 heads of 128) selects the top 2,048 cached tokens a
query; 33 `sliding_attention` layers of a latent attention of their own (64
heads, q_lora 1,024, kv_lora 1,024, qk 192 + 64, v 128, base 5e4) over a window
of 513; a headwise output gate on both; `apply_mla_qkv_lora_rescale`; a dense
SwiGLU of 13,824 in the first layer, then 256 routed experts of 1,536 (top-8,
sigmoid router with a selection bias, renormalised, scaled by 1) beside one
shared expert; vocabulary 152,064, untied head.

Source: https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json

This preset is the PUBLISHED configuration whole (288 B parameters), which no
machine this repo runs on holds. It exists so that the widths are written
once; what runs is a cut of it (fewer layers, the experts and vocabulary rows
one chip of an expert-parallel deployment holds), and the cut lives with
whoever makes it: `benchmarks/configs/dots3_note_ep16.json` (`overrides`), or
`--set model_config.n_layer=5 ...`. The family is SERVED (sample.py
--engine=continuous, ServeEngine) from two kinds of latent paged cache;
`launch.py` refuses it by name (models/dots3.py `check_training`), so the
optimizer fields below are the Kimi preset's and mean nothing here.
`block_size` 65,536 is this repo's serving cap on prompt + output (the source
declares 524,288 positions). Left out: the vision and audio towers, the MTP module.
"""

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.dots3 import FULL, SLIDING, Dots3Config

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
    model_config=Dots3Config(
        block_size=65536,
        vocab_size=152064,
        n_layer=46,
        n_head=128,
        n_embd=5120,
        # the published list: two full layers, then eleven periods of three sliding layers and a full one
        layer_types=(FULL, FULL) + (SLIDING, SLIDING, SLIDING, FULL) * 11,
        n_dense_layers=1,
        q_lora_rank=1024,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=8e7,
        swa_n_head=64,
        swa_q_lora_rank=1024,
        swa_kv_lora_rank=1024,
        swa_qk_nope_head_dim=192,
        swa_qk_rope_head_dim=64,
        swa_v_head_dim=128,
        swa_rope_theta=5e4,
        sliding_window=513,
        index_n_heads=64,
        index_head_dim=128,
        index_topk=2048,
        mla_rescale=True,
        headwise_gate=True,
        dense_width=13824,
        n_experts=256,
        n_experts_held=256,
        expert_offset=0,
        moe_top_k=8,
        expert_width=1536,
        n_shared_experts=1,
        routed_scaling_factor=1.0,
        moe_renormalize=True,
        rms_norm_eps=1e-5,
    ),
)
