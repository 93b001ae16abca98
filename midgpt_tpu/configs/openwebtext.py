"""124M GPT-2-small shape, single host (reference configs/openwebtext.py:4-21)."""

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.gpt import GPTConfig

config = ExperimentConfig(
    rundir="",
    data_dir="data/openwebtext",
    learning_rate=1e-3,
    batch_size=128,
    warmup_steps=5_000,
    min_lr=1e-5,
    lr_decay_steps=60_000,
    max_steps=60_000,
    beta2=0.95,
    weight_decay=1e-4,
    eval_interval=1000,
    compute_dtype="bfloat16",
    param_dtype="float32",
    g_accum_iters=16,  # effective batch 2048
    shard_model=False,
    mesh=MeshConfig(data=-1, fsdp=1, sp=1),
    model_config=GPTConfig(
        block_size=1024, vocab_size=50304, n_layer=12, n_head=12, n_embd=768,
        dropout=0.0,
        # Same function as the reference rotation via the in-graph q/k row
        # permutation (models/gpt.py _qkv_weights, exactness test-pinned);
        # contiguous rotate-half instead of stride-2 gathers. `train_124m`
        # (ledger) runs it; the interleaved form runs in the XL cells.
        rope_style="split",
    ),
)
