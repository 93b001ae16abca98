"""granite-4.0-h-micro as published: 40 layers, `layer_types` with `attention`
at 5, 15, 25, 35 and `mamba` elsewhere (a period of ten: five mamba layers, the
attention layer, four mamba layers), hidden 2,048; the mamba layers Mamba-2 with
64 heads of 64 (`mamba_expand` 2), `mamba_d_state` 128, one group, a convolution
of 4 taps with a bias, chunks of 256; the attention layers 32 query heads on 8
K/V heads of 64 with no position signal (`position_embedding_type` nope) at
`attention_multiplier` 1/64; a gated MLP of 8,192 after every mixer;
`embedding_multiplier` 12, `residual_multiplier` 0.22, `logits_scaling` 8;
RMSNorm eps 1e-5 on each branch's input; vocabulary 100,352, the head tied.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json

36 x 76,182,976 + 4 x 60,821,504 + 205,520,896 + 2,048 = 3,191,396,096
parameters, 6.38 GB in bfloat16: it FITS one chip, and the benchmark's
configuration (benchmarks/configs/granite_4_0_h_micro.json) cuts nothing. The
family is SERVED (sample.py --engine=continuous, ServeEngine); `launch.py`
refuses it by name (models/granite_hybrid.py `check_training`), so the optimizer
fields below are the Kimi preset's and mean nothing here. `block_size` is the
source's 131,072 positions, taken as the serving cap on prompt + output.
"""

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.granite_hybrid import GraniteHybridConfig

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
    model_config=GraniteHybridConfig(
        block_size=131072,
        vocab_size=100352,
        n_layer=40,
        n_head=32,
        n_embd=2048,
        n_kv_head=8,
        layer_types=(("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4,
        mamba_heads=64,
        mamba_head_dim=64,
        mamba_state=128,
        mamba_groups=1,
        mamba_conv=4,
        mamba_chunk=256,
        dense_width=8192,
        embedding_multiplier=12.0,
        attention_multiplier=0.015625,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        rms_norm_eps=1e-5,
    ),
)
