"""610M wide-head (C=128) slice of GPT-2-XL, as a config for one chip.

GPT-2-XL width (n_embd=2048, n_head=16 → head dim C=128) at 8 layers, so
fp32 master params + Adam state + remat-free activations fit one v5e chip
(15.75 GB). C=128 fills the MXU's 128-wide systolic array on QK^T/PV where
the GPT-2-small C=64 uses half of it. Its speed has no cell on the current
toolchain (both one-chip GPT cells have C=64): PERF.md §7 row 7.

    python launch.py --config=wide610m --rundir=outputs/wide

Optimizer/schedule constants follow the openwebtext_xl recipe (reference
configs/openwebtext_xl.py:4-22) with the horizon scaled to a single chip.
"""

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.gpt import GPTConfig

config = ExperimentConfig(
    rundir="",
    data_dir="data/local_text",
    learning_rate=1e-3,
    batch_size=12,  # 16 is under HBM pressure on one chip; no cell: PERF.md §7 row 7
    warmup_steps=300,
    min_lr=1e-5,
    lr_decay_steps=3000,
    max_steps=3000,
    beta2=0.95,
    weight_decay=1e-4,
    eval_interval=250,
    eval_steps=50,
    compute_dtype="bfloat16",
    param_dtype="float32",
    g_accum_iters=1,
    shard_model=False,
    mesh=MeshConfig(data=-1, fsdp=1, sp=1),
    model_config=GPTConfig(
        block_size=1024,
        vocab_size=50304,
        n_layer=8,
        n_head=16,
        n_embd=2048,
        dropout=0.0,
        attn_impl="flash",
        # Remat OFF fits at batch 12 and recomputes nothing (no cell:
        # PERF.md §7 row 7).
        remat=False,
        remat_policy="flash",
        # Like the 124M recipe: remat-off only FITS with the layer scan
        # fully unrolled — the rolled scan's
        # per-iteration temps exceed HBM (OOMs at unroll=1).
        scan_unroll=8,
        rope_style="split",
        # Head-major end to end: at C=128 a head's row is a whole 128-lane
        # run, so the layout needs no relayout around the kernel; at C=64 it
        # leaves half-lane runs — keep 'seq' there (`train_124m`, ledger).
        # No cell at C=128: PERF.md §7 row 7.
        attn_layout="head",
    ),
)
