"""Trinity-Mini (arcee-ai, `model_type: afmoe`, 26B-A3B) as published: 32
layers (three sliding-window layers of 2,048 with rotary, then one global layer
with NO position signal, eight times over), hidden 2,048, 32 query heads over 4
K/V heads of 128, QK-norm, an output gate on attention, four RMSNorms a layer in
sandwich position, a muP-scaled embedding, two dense SwiGLU layers of 6,144
then 128 routed experts of 1,024 (top-8, sigmoid router with a selection bias,
renormalised, scaled by 2.826) beside one shared expert, vocabulary 200,192,
untied head.

Source: https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json

This preset is the PUBLISHED configuration whole (26.1 B parameters, 52 GB in
bfloat16), which no machine this repo runs on holds. It exists so that the
widths are written once; what runs is a cut of it in DEPTH (an expert layer is
held whole: all 128 experts are 1.68 GB), and the cut lives with whoever makes
it: `benchmarks/configs/trinity_mini_pp.json` (`overrides`), or `--set
model_config.n_layer=5 ...`. The family is SERVED (sample.py
--engine=continuous, ServeEngine); `launch.py` refuses it by name
(models/trinity.py `check_training`), so the optimizer fields below are the
Kimi preset's and mean nothing here. `block_size` 16,384 is this repo's serving
cap (the source declares 131,072 positions).
"""

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.trinity import FULL, SLIDING, TrinityConfig

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
    model_config=TrinityConfig(
        block_size=16384,
        vocab_size=200192,
        n_layer=32,
        n_head=32,
        n_embd=2048,
        layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 8,
        n_dense_layers=2,
        head_dim=128,
        n_kv_heads=4,
        rope_theta=1e4,
        sliding_window=2048,
        dense_width=6144,
        n_experts=128,
        n_experts_held=128,
        expert_offset=0,
        moe_top_k=8,
        expert_width=1024,
        n_shared_experts=1,
        route_scale=2.826,
        route_norm=True,
        mup_enabled=True,
        rms_norm_eps=1e-5,
    ),
)
