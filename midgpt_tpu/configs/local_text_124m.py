"""124M flagship shape on the offline-BPE local_text corpus, single chip.

The full openwebtext recipe (configs/openwebtext.py; reference
configs/openwebtext.py:4-21) scaled to a single v5e chip and a ~2h horizon:
identical model shape (GPT-2-small, vocab padded to 50304), identical
optimizer constants (lr 1e-3 cosine to 1e-5, beta2 0.95, wd 1e-4 with
wd/lr decoupling), the full fast path (flash attention, remat off — it
fits at this scale: the `train_124m` cell runs this recipe — fused CE) and
the G=16 accumulation schedule — with effective batch 256 (16 x 16)
instead of 2048 and the warmup/decay horizon scaled to 3000
steps. Data comes from data/local_text/prepare.py (offline-trained
byte-level BPE over local text trees).
"""

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.gpt import GPTConfig

config = ExperimentConfig(
    rundir="",
    data_dir="data/local_text",
    learning_rate=1e-3,
    batch_size=16,
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
    g_accum_iters=16,  # effective batch 256
    shard_model=False,
    mesh=MeshConfig(data=-1, fsdp=1, sp=1),
    # Serving: 4-of-12-layer self-draft speculation for sample.py
    # --engine=continuous (override with --spec_layers; docs/SERVING.md).
    spec_layers=4,
    model_config=GPTConfig(
        block_size=1024,
        vocab_size=50304,
        n_layer=12,
        n_head=12,
        n_embd=768,
        dropout=0.0,
        attn_impl="flash",
        # 124M at microbatch 16 fits the 15.75 GB chip WITHOUT per-block
        # remat, so nothing is recomputed (this recipe IS the `train_124m`
        # cell: its MFU is in the ledger; remat-on has no cell);
        # keep the policy name so
        # `--set model_config.remat=True` restores it for tighter chips.
        remat=False,
        remat_policy="flash",
        # Remat-off only FITS with the layer scan fully unrolled (the
        # `train_124m` cell's setting): the rolled scan's per-iteration temps push the
        # no-remat activation set past 15.75 GB (OOMs at unroll=1).
        scan_unroll=12,
        rope_style="split",  # same-function fast RoPE (see openwebtext.py)
    ),
)
