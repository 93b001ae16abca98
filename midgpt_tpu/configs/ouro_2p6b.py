"""Ouro-2.6B as published: 48 layers applied 4 times (`total_ut_steps`), hidden
2,048, 16 heads = 16 K/V heads of 128, SwiGLU 5,632, four weighted RMSNorms a
layer in sandwich position (eps 1e-6), rotate-half rotary over all 128 channels
at base 1e6, an exit gate after every pass (`early_exit_threshold` 1: the last
pass's logits are served), vocabulary 49,152, untied head.

Source: https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json

2.668 B parameters, 5.34 GB in bfloat16: the whole model fits one chip on the
serving path, so nothing is cut. The paged cache has 4 x 48 = 192 layers
(1,572,864 B a token in bf16). The family is SERVED (sample.py
--engine=continuous, ServeEngine); `launch.py` refuses it by name
(models/ouro.py `check_training`), so the optimizer fields below are the Kimi
preset's and mean nothing here. `block_size` is the source's 65,536 positions,
taken as the serving cap on prompt + output.
"""

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.ouro import OuroConfig

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
    model_config=OuroConfig(
        block_size=65536,
        vocab_size=49152,
        n_layer=48,
        n_head=16,
        n_embd=2048,
        n_loop=4,
        head_dim=128,
        dense_width=5632,
        rope_theta=1e6,
        rms_norm_eps=1e-6,
        early_exit_threshold=1.0,
    ),
)
