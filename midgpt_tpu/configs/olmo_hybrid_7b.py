"""Olmo-Hybrid-7B as published: 32 layers, `layer_types` (linear_attention x 3,
full_attention) x 8, hidden 3,840; the linear layers Gated DeltaNet with 30 key
heads of 96 and 30 value heads of 192, a short convolution of 4 taps and
`linear_allow_neg_eigval` true; the full layers 30 heads of 128 with no rotary
(`rope_theta` null); SwiGLU 11,008; RMSNorm eps 1e-6 on each branch's output;
vocabulary 100,352, untied head.

Source: https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json

24 x 215,570,172 + 8 x 185,809,920 + 770,703,360 + 3,840 = 7,430,870,808
parameters, 14.86 GB in bfloat16: it builds under `jax.eval_shape` only. What
runs on a chip is the first 16 layers, stage one of a two-stage pipeline of
whole layers (benchmarks/configs/olmo_hybrid_7b_pp2.json). The family is SERVED
(sample.py --engine=continuous, ServeEngine); `launch.py` refuses it by name
(models/olmo_hybrid.py `check_training`), so the optimizer fields below are the
Kimi preset's and mean nothing here. `block_size` is the source's 65,536
positions, taken as the serving cap on prompt + output.
"""

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.olmo_hybrid import OlmoHybridConfig

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
    model_config=OlmoHybridConfig(
        block_size=65536,
        vocab_size=100352,
        n_layer=32,
        n_head=30,
        n_embd=3840,
        layer_types=("linear_attention", "linear_attention", "linear_attention", "full_attention") * 8,
        linear_heads=30,
        linear_key_dim=96,
        linear_value_dim=192,
        conv_kernel=4,
        allow_neg_eigval=True,
        dense_width=11008,
        rms_norm_eps=1e-6,
    ),
)
