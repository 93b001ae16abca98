"""Experiment configuration (mirrors reference train.py:26-44 + launch.py persistence).

Configs are plain frozen dataclasses; named presets live in
`midgpt_tpu/configs/*.py` as modules exposing a module-level `config`, loaded
by name (same UX as reference launch.py:25-27). `to_json`/`from_json` give the
rundir round-trip that sample-time reconstruction depends on (reference
launch.py:55-57, sample.py:49-65).
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import math
import typing as tp

from midgpt_tpu.models.gpt import GPTConfig


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical 5D device mesh. Axis sizes of -1 are inferred at runtime.

    The reference hard-codes Mesh((n_devices // 8, 8), ('replica', 'data'))
    (reference train.py:130) — i.e. batch over both axes, params over the
    8-wide axis. Here the axes are named for their role: batch shards over
    ('data', 'fsdp'), params over 'fsdp', the sequence axis over 'sp'
    (context parallelism — ring or Ulysses attention; 1 unless one of them
    is on), the block projections' feature axes over 'tp' (Megatron tensor
    parallelism, parallel/tp.py), and the LAYER axis over 'pp' (GPipe
    pipeline stages, parallel/pipeline.py) — both 1 unless enabled.
    """

    data: int = -1  # -1: infer as n_devices // (fsdp * sp * tp * pp * ep)
    fsdp: int = 8
    sp: int = 1
    tp: int = 1  # tensor parallelism (Megatron column/row, parallel/tp.py)
    pp: int = 1  # pipeline parallelism (GPipe over stages, parallel/pipeline.py)
    ep: int = 1  # expert parallelism (MoE expert axis, models/gpt.py MoEParams)


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    rundir: str
    data_dir: str
    learning_rate: float
    batch_size: int  # GLOBAL batch size across all devices
    warmup_steps: int
    min_lr: float
    lr_decay_steps: int
    max_steps: int
    beta2: float
    weight_decay: float
    eval_interval: int
    param_dtype: str  # 'float32'
    compute_dtype: str  # 'bfloat16'
    g_accum_iters: int
    shard_model: bool
    model_config: tp.Any  # GPTConfig, or another family's config (models/kimi_linear.py)
    mesh: MeshConfig = MeshConfig()
    eval_steps: int = 200  # batches per eval (reference train.py:110)
    # Max eval batches materialized on host / staged to device at once
    # (training/train.py evaluate): bounds host memory to
    # eval_host_chunk x local_batch x T int32 per split pass.
    eval_host_chunk: int = 25
    log_interval: int = 20
    seed: int = 0
    data_seed: int = 1337  # seeded, resumable data sampler (reference has none)
    fsdp_min_size: int = 2**18  # shard only params bigger than this (reference model.py:171)
    # Token-chunk size of the fused lm_head+CE loss (ops/loss.py): bounds the
    # f32 logits buffer to chunk×V instead of B·T×V.
    loss_chunk_tokens: int = 8192
    # Recompute chunk logits in backward (caps live memory at one chunk x V
    # buffer). None = auto (on past 8 chunks per microbatch — ops/loss.py);
    # False forces storing the bf16 chunk logits (faster at single-chip
    # scales), True forces recompute for memory-tight shapes.
    loss_remat_chunks: tp.Optional[bool] = None
    # Which collective schedule the FSDP step compiles to. 'auto' (default):
    # derived from the mesh the run was given and the model it trains
    # (`fsdp_schedule` below) — the authored ZeRO-3 schedule wherever it
    # composes (explicit per-layer all-gather / grad reduce-scatter,
    # parallel/shard_map_fsdp.py), else the compiler's (sharding constraints
    # in, GSPMD-chosen collectives out: reference parity). 'shard_map' and
    # 'gspmd' force one side: the handle the parity tests compare the two
    # lowerings with, not a tuning knob.
    fsdp_mode: str = "auto"
    # MoE router load-balance auxiliary loss (Switch Transformer eq. 4-6):
    # training loss becomes CE + moe_aux_coef * aux, with aux the
    # layer-mean of E * sum_e P_e * f_e (models/gpt.py _moe_gates). 0.0
    # (default) keeps the loss byte-identical to the pre-knob path — the
    # aux computation is never requested, so XLA never sees it (zero-impact
    # pin in tests/test_moe.py). Switch uses 1e-2.
    moe_aux_coef: float = 0.0
    # With mesh.tp > 1: also shard wte/lm_head's vocab axis over 'tp'
    # (Megatron vocab-parallel embedding + CE, parallel/tp.py). No effect at
    # tp=1.
    tp_vocab: bool = True
    # With mesh.pp > 1: number of GPipe microbatches per step (0 = one per
    # pipeline stage). More microbatches shrink the pipeline bubble
    # (pp-1 of M+pp-1 ticks) at the cost of smaller per-tick matmuls.
    pipeline_microbatches: int = 0
    # 'gpipe' (reverse-AD backward, stash grows with microbatches) or
    # '1f1b' (interleaved fwd/bwd, 2*pp-slot stash independent of
    # microbatch count — parallel/pipeline.py make_pipeline_loss_and_grad).
    pipeline_schedule: str = "gpipe"
    # ---- robustness (midgpt_tpu/robustness, docs/ROBUSTNESS.md) ----
    # Constant added to the loop iteration before it indexes the positional
    # data sampler / dropout-key stream. The supervisor advances it on a
    # divergence rollback so the resumed run samples PAST the poisoned data
    # window; 0 (default) is the plain trajectory.
    data_step_offset: int = 0
    # Divergence-restart budget of supervisor.supervise (0 disables
    # rollback: the first divergence raises straight through, the pre-PR
    # behavior).
    max_restarts: int = 2
    # Base of the supervisor's exponential restart backoff (sleep
    # restart_backoff_sec * 2**attempt between rollbacks).
    restart_backoff_sec: float = 1.0
    # Verified checkpoints kept on disk. 2 (not 1): the previous checkpoint
    # must outlive the next save's verification, or a crash mid-save can
    # destroy the only good state.
    ckpt_max_to_keep: int = 2
    # Retry budget / backoff base for the synchronous part of a checkpoint
    # save (transient TensorStore/filesystem failures).
    ckpt_write_retries: int = 3
    ckpt_retry_backoff_sec: float = 0.5
    # Poll the preemption flag every N steps. 1 is free single-process; on
    # multihost every check is a tiny cross-host all-gather (robustness/
    # preempt.py), so large fleets may want a coarser cadence.
    preempt_check_interval: int = 1
    # Fault-injection plan ("kind[@step][*times],..." — robustness/faults.py),
    # activated once per supervised run; "" (default) injects nothing.
    fault_plan: str = ""
    # Hung-step watchdog (robustness/watchdog.py): deadline in seconds armed
    # around each of the train loop's device syncs (the t_land force points).
    # 0.0 (default) disables the guard entirely — the sync is a plain call,
    # no thread, no clock read. Production runs want ~300s (a few
    # compiles' worth of slack above the longest healthy step).
    watchdog_deadline_s: float = 0.0
    # What an expired watchdog does after dumping the flight recorder:
    # 'raise' raises StepHangError (the supervisor restarts from the last
    # verified checkpoint, like a divergence); 'exit' hard-exits with
    # watchdog.EXIT_CODE for a cluster layer that restarts whole processes.
    watchdog_escalate: str = "raise"
    # Topology-change policy when a supervised run resumes onto a mesh with
    # a different device count than the ledger recorded (elastic resume —
    # docs/ROBUSTNESS.md "Elastic resume & watchdog"): 'same' (default)
    # refuses loudly; 'any' re-derives the data/fsdp axes and restores the
    # checkpoint through the new mesh's shardings.
    on_resume_mesh: str = "same"
    # Grace budget for the SIGTERM emergency save, seconds from the signal's
    # arrival. If the step boundary where the save WOULD start is already
    # past the budget, the save is skipped loudly (ledger note + flight-
    # recorder dump) instead of being killed mid-write and leaving an
    # unverified partial. 0.0 (default) = unbounded (always attempt).
    preempt_grace_s: float = 0.0
    # ---- speculative decoding (sampling/spec.py, docs/SERVING.md) ----
    # Self-draft depth for sampling/serving: the first spec_layers blocks of
    # the model (sharing its embeddings/lm_head) propose tokens that the
    # full model verifies in one batched paged forward. 0 (default)
    # disables speculation — plain continuous-batching decode. Training is
    # untouched by these knobs; sample.py --spec_layers overrides.
    spec_layers: int = 0
    # Bounds of the per-slot adaptive draft length k (both powers of two,
    # like the decode-chunk buckets): the serve scheduler doubles/halves a
    # slot's k from its recent acceptance EMA within [spec_k_min,
    # spec_k_max]; spec_adapt=False pins k at spec_k_max.
    spec_k_max: int = 4
    spec_k_min: int = 1
    spec_adapt: bool = True
    # Paged-KV-cache storage dtype for the serving engine (sampling/serve.py
    # ServeEngine cache_dtype; docs/SERVING.md "Quantized KV cache").
    # 'bf16' (default) stores pages in bf16; 'int8' stores them int8 with
    # f32 absmax scales in a small side buffer — decode-attention HBM
    # traffic halves and a byte-budgeted pool admits 2x the pages. Training
    # is untouched; sample.py --kv_dtype overrides.
    kv_cache_dtype: str = "bf16"
    debug: bool = False

    def __post_init__(self):
        # Fail at construction, not at trace time deep inside the first step.
        mc = self.model_config
        if not (0.0 < self.beta2 < 1.0):
            # beta2 >= 1 makes adam's bias correction divide by zero on step
            # 1 — a NaN source INSIDE the optimizer that the train step's
            # grad-norm health check cannot see (its soundness induction
            # assumes the chain maps finite state+grads to finite updates).
            raise ValueError(f"beta2={self.beta2} must be in (0, 1)")
        if self.fsdp_mode not in ("auto", "gspmd", "shard_map"):
            # A typo would silently run the derived schedule — fail at
            # construction like qkv_proj/rope_style.
            raise ValueError(
                f"unknown fsdp_mode {self.fsdp_mode!r} ('auto', 'gspmd' or 'shard_map')"
            )
        if self.fsdp_mode == "shard_map":
            # Forcing the authored schedule where it does not compose fails
            # loudly; left to 'auto', the same combinations fall back to the
            # compiler's schedule (fsdp_schedule).
            axes = {a: max(getattr(self.mesh, a), 1) for a in ("sp", "tp", "pp", "ep")}
            why = self.authored_fsdp_refusal(axes)
            if why is not None:
                raise ValueError(f"fsdp_mode='shard_map' {why}")
        if not 0 <= self.spec_layers < mc.n_layer:
            # spec_layers == n_layer would "draft" with the target itself —
            # all cost, no amortization — and deeper is shape-invalid.
            raise ValueError(
                f"spec_layers={self.spec_layers} must be in [0, n_layer="
                f"{mc.n_layer})"
            )
        for k_name, k_val in (("spec_k_max", self.spec_k_max),
                              ("spec_k_min", self.spec_k_min)):
            if k_val < 1 or k_val & (k_val - 1):
                # non-pow2 k would mint a fresh draft+verify program pair
                # per value instead of riding the bucketed compile set
                # (sampling/serve.py _spec_round)
                raise ValueError(f"{k_name}={k_val} must be a power of two")
        if self.spec_k_min > self.spec_k_max:
            raise ValueError(
                f"spec_k_min={self.spec_k_min} > spec_k_max={self.spec_k_max}"
            )
        if self.kv_cache_dtype not in ("bf16", "int8"):
            # A typo would silently serve from a bf16 pool the operator
            # believed was quantized (half the expected page capacity at a
            # byte budget) — fail at construction like the other enums.
            raise ValueError(
                f"unknown kv_cache_dtype {self.kv_cache_dtype!r} "
                "('bf16' or 'int8')"
            )
        if self.data_step_offset < 0:
            # A negative offset would re-sample windows already consumed
            # before the rollback — the exact data the skip exists to avoid.
            raise ValueError(f"data_step_offset={self.data_step_offset} must be >= 0")
        if self.max_restarts < 0:
            raise ValueError(f"max_restarts={self.max_restarts} must be >= 0")
        if self.ckpt_max_to_keep < 1:
            raise ValueError(f"ckpt_max_to_keep={self.ckpt_max_to_keep} must be >= 1")
        if self.ckpt_write_retries < 1:
            raise ValueError(f"ckpt_write_retries={self.ckpt_write_retries} must be >= 1")
        if self.preempt_check_interval < 1:
            raise ValueError(
                f"preempt_check_interval={self.preempt_check_interval} must be >= 1"
            )
        if self.restart_backoff_sec < 0 or self.ckpt_retry_backoff_sec < 0:
            raise ValueError("backoff seconds must be >= 0")
        if self.watchdog_deadline_s < 0:
            # Negative would arm a guard that expires before the first poll
            # — every step "hangs". 0 is the documented off switch.
            raise ValueError(
                f"watchdog_deadline_s={self.watchdog_deadline_s} must be "
                ">= 0 (0 disables the watchdog)"
            )
        if self.watchdog_escalate not in ("raise", "exit"):
            raise ValueError(
                f"unknown watchdog_escalate {self.watchdog_escalate!r} "
                "('raise' or 'exit')"
            )
        if self.on_resume_mesh not in ("same", "any"):
            raise ValueError(
                f"unknown on_resume_mesh {self.on_resume_mesh!r} "
                "('same' or 'any')"
            )
        if self.preempt_grace_s < 0:
            raise ValueError(
                f"preempt_grace_s={self.preempt_grace_s} must be >= 0 "
                "(0 = unbounded)"
            )
        # The model family's own rules (every family's config has
        # `check_experiment`: models/__init__.py): the GPT's are
        # `check_gpt_family` below, reached through `GPTConfig`.
        mc.check_experiment(self)

    def check_gpt_family(self) -> None:
        """The GPT's rules (`GPTConfig.check_experiment`): its own knobs and
        how they compose with the mesh axes and schedules of this config."""
        mc = self.model_config
        if mc.qkv_proj not in ("fused", "split3"):
            # A typo here would silently fall back to the fused lowering AND
            # bypass the tp auto-switch (training/train.py) — fail loudly.
            raise ValueError(f"unknown qkv_proj {mc.qkv_proj!r} ('fused' or 'split3')")
        if mc.rope_style not in ("interleaved", "split"):
            # A typo would silently run the interleaved rotation on weights
            # the caller expected permuted (or vice versa) — wrong math that
            # trains; fail at construction like qkv_proj.
            raise ValueError(
                f"unknown rope_style {mc.rope_style!r} ('interleaved' or 'split')"
            )
        if mc.rope_style == "split" and mc.head_dim % 2 != 0:
            raise ValueError("rope_style='split' needs an even head_dim")
        if mc.attn_layout not in ("seq", "head"):
            raise ValueError(
                f"unknown attn_layout {mc.attn_layout!r} ('seq' or 'head')"
            )
        if mc.dropout > 0.0 and mc.attn_impl != "naive":
            # attention-probability dropout exists only on the naive path
            # (ops/attention.py dispatch)
            raise ValueError(
                f"attn_impl={mc.attn_impl!r} does not support attention "
                f"dropout (dropout={mc.dropout}); use attn_impl='naive' or "
                "set dropout=0.0"
            )
        tp = self.mesh.tp
        if tp == -1:
            tp = 1  # the documented "infer at runtime" sentinel (make_mesh)
        if tp < 1:
            raise ValueError(f"mesh.tp={tp} must be >= 1 (or -1 to infer)")
        if tp > 1:
            # Megatron sharding needs whole heads / whole MLP columns per
            # tp shard, and composes only with the GSPMD schedule for now.
            if mc.n_head % tp != 0:
                raise ValueError(f"n_head={mc.n_head} not divisible by mesh.tp={tp}")
            if mc.kv_heads % tp != 0:
                # GQA: the wkv column shard and the serving pool both split
                # on whole KV heads (parallel/tp.py, parallel/serve_tp.py).
                raise ValueError(
                    f"n_kv_heads={mc.kv_heads} not divisible by mesh.tp={tp} "
                    "— tp shards whole KV heads"
                )
            if (4 * mc.n_embd) % tp != 0:
                raise ValueError(f"4*n_embd={4 * mc.n_embd} not divisible by mesh.tp={tp}")
            if self.tp_vocab and mc.vocab_size % tp != 0 and self.mesh.pp in (1, -1):
                # Under pp the pipeline never vocab-shards (its CE runs on
                # gathered heads; pipeline_param_specs keeps wte/lm_head
                # tp-replicated), so tp_vocab is inert there — don't reject
                # a config the pp x tp path runs correctly.
                raise ValueError(
                    f"vocab_size={mc.vocab_size} not divisible by mesh.tp={tp} "
                    "(set tp_vocab=False or pad the vocab)"
                )
        pp = self.mesh.pp
        if pp == -1:
            pp = 1
        if pp < 1:
            raise ValueError(f"mesh.pp={pp} must be >= 1 (or -1 to infer)")
        if self.pipeline_microbatches < 0:
            raise ValueError(f"pipeline_microbatches={self.pipeline_microbatches} must be >= 0")
        if self.pipeline_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"unknown pipeline_schedule {self.pipeline_schedule!r} "
                "('gpipe' or '1f1b')"
            )
        if self.pipeline_schedule == "1f1b" and self.mesh.tp not in (1, -1):
            raise ValueError(
                "pipeline_schedule='1f1b' does not compose with mesh.tp > 1 "
                "yet (its backward is hand-written; use 'gpipe')"
            )
        if pp > 1:
            # GPipe composes with 'data', 'fsdp' (v2: stage weights shard,
            # per-layer gathers in the stage scan) and 'tp' (r5: the
            # Megatron axes of the stage weights shard over a GSPMD 'auto'
            # axis inside the pipeline shard_map — parallel/pipeline.py).
            # sp composition is future work.
            if mc.n_layer % pp != 0:
                raise ValueError(f"n_layer={mc.n_layer} not divisible by mesh.pp={pp}")
            if mc.dropout != 0.0:
                raise ValueError("mesh.pp > 1 requires dropout=0.0")
            if self.mesh.sp not in (1, -1):
                raise ValueError(
                    "mesh.pp > 1 does not compose with mesh.sp > 1 yet "
                    "(set sp=1)"
                )
            if mc.attn_impl in ("ring", "ulysses"):
                raise ValueError("mesh.pp > 1 does not compose with sequence parallelism yet")
            mb = self.pipeline_microbatches or pp
            # Necessary but not sufficient: the runtime constraint is on the
            # per-data-shard LOCAL batch, unknowable here (data may be -1);
            # make_pipeline_loss raises a config-pointing ValueError then.
            if self.batch_size % mb != 0:
                raise ValueError(
                    f"batch_size={self.batch_size} not divisible by "
                    f"pipeline_microbatches={mb}"
                )
        if self.moe_aux_coef != 0.0:
            if mc.n_experts == 0:
                raise ValueError(
                    f"moe_aux_coef={self.moe_aux_coef} needs a routed MLP "
                    "(n_experts > 0)"
                )
            if self.mesh.pp not in (1, -1):
                # The aux term threads through GPT.hidden(return_moe_aux=True),
                # which only the implicit-GSPMD loss calls (the authored
                # ZeRO-3 loss is refused in authored_fsdp_refusal); the
                # pipeline body has its own layer loop. Fail loudly instead
                # of silently training without balance pressure.
                raise ValueError(
                    "moe_aux_coef requires mesh.pp == 1 (the aux term is only "
                    "folded into the implicit-GSPMD loss)"
                )
        ep = self.mesh.ep
        if ep == -1:
            ep = 1
        if mc.n_experts < 0:
            raise ValueError(f"n_experts={mc.n_experts} must be >= 0")
        if mc.n_experts > 0:
            if not (1 <= mc.moe_top_k <= mc.n_experts):
                raise ValueError(
                    f"moe_top_k={mc.moe_top_k} must be in [1, n_experts="
                    f"{mc.n_experts}]"
                )
            if pp > 1:
                raise ValueError(
                    "MoE (n_experts > 0) does not compose with mesh.pp > 1 yet"
                )
        if ep > 1:
            if mc.n_experts == 0 or mc.n_experts % ep != 0:
                raise ValueError(
                    f"mesh.ep={ep} needs n_experts ({mc.n_experts}) divisible by it"
                )
        sp = self.mesh.sp
        if sp == -1:
            sp = 1
        if mc.attn_impl == "ulysses":
            # Ulysses re-shards heads over sp (after any tp head sharding):
            # every (tp, sp) device needs whole heads.
            if sp > 1 and mc.n_head % (tp * sp) != 0:
                raise ValueError(
                    f"attn_impl='ulysses' needs n_head % (tp*sp) == 0, got "
                    f"n_head={mc.n_head}, tp={tp}, sp={sp}"
                )

    def authored_fsdp_refusal(self, axes: tp.Mapping[str, int]) -> tp.Optional[str]:
        """Why the authored ZeRO-3 loss (parallel/shard_map_fsdp.py) does not
        compose with this config on a mesh whose axis sizes are `axes`
        (`Mesh.shape`, or the configured sizes with -1 read as 1), or None
        where it does. The one statement of the rule: forcing
        fsdp_mode='shard_map' raises with it, 'auto' falls back on it."""
        mc = self.model_config
        if not isinstance(mc, GPTConfig):
            return (
                "is written over the GPT's layer scan (GPT.hidden "
                "layer_scan); this model family trains under the "
                "compiler's schedule (gspmd)"
            )
        if axes["pp"] > 1:
            return (
                "does not compose with mesh.pp > 1 (the pipeline's stages "
                "gather their own weights; it requires gspmd)"
            )
        if axes["ep"] > 1:
            return "does not compose with mesh.ep > 1 (expert parallelism requires gspmd)"
        if self.moe_aux_coef != 0.0:
            return (
                "does not compose with moe_aux_coef (the aux term is only "
                "folded into the implicit-GSPMD loss: gspmd)"
            )
        if axes["tp"] > 1 and (axes["sp"] > 1 or mc.attn_impl in ("ring", "ulysses")):
            # r5: the explicit ZeRO-3 body composes with tp (auto-axis GSPMD
            # inside, parallel/shard_map_fsdp.py) — but not yet together
            # with its sequence-parallel schedules.
            return (
                "with mesh.tp > 1 does not compose with sequence parallelism "
                "yet (set sp=1 and a non-ring/ulysses attn_impl)"
            )
        return None

    def fsdp_schedule(self, mesh_shape: tp.Mapping[str, int]) -> str:
        """The collective schedule the FSDP step compiles to on a mesh of this
        shape ({axis: size}, as `Mesh.shape` gives it): 'authored' (per-layer
        weight all-gathers whose transpose is the per-layer gradient
        reduce-scatter, parallel/shard_map_fsdp.py) or 'compiler' (the
        implicit-GSPMD loss). One algorithm, ZeRO-3, two ways to lower it,
        chosen by what the program can observe: authored wherever it composes
        on more than one device; the compiler's everywhere else (pipeline,
        expert parallel, the MoE aux loss, another model family) and on EVERY
        one-device mesh, whose step program has no collective to author."""
        if self.fsdp_mode != "auto":
            return "authored" if self.fsdp_mode == "shard_map" else "compiler"
        if math.prod(mesh_shape.values()) > 1 and self.authored_fsdp_refusal(mesh_shape) is None:
            return "authored"
        return "compiler"

    def replace(self, **kw) -> "ExperimentConfig":
        return dataclasses.replace(self, **kw)


def to_json(config: ExperimentConfig) -> str:
    return json.dumps(dataclasses.asdict(config), indent=2)


# Model families: the `family` a config.json's `model_config` names (absent:
# the GPT, which every older rundir holds) -> "module:class" of its config
# dataclass. The one place a family is registered; a family's module is
# imported when a config names it, not with this module.
MODEL_FAMILIES: tp.Dict[str, str] = {
    "gpt": "midgpt_tpu.models.gpt:GPTConfig",
    "kimi_linear": "midgpt_tpu.models.kimi_linear:KimiLinearConfig",
    "mimo_v2": "midgpt_tpu.models.mimo_v2:MimoV2Config",
    "pangu_ultra": "midgpt_tpu.models.pangu_ultra:PanguUltraConfig",
    "ouro": "midgpt_tpu.models.ouro:OuroConfig",
    "afmoe": "midgpt_tpu.models.trinity:TrinityConfig",
    "dots3_note": "midgpt_tpu.models.dots3:Dots3Config",
    "olmo_hybrid": "midgpt_tpu.models.olmo_hybrid:OlmoHybridConfig",
    "granite_hybrid": "midgpt_tpu.models.granite_hybrid:GraniteHybridConfig",
}


def _model_config_class(raw: dict) -> type:
    family = raw.get("family", "gpt")
    if family not in MODEL_FAMILIES:
        raise ValueError(
            f"config.json names model family {family!r}; this checkout has {sorted(MODEL_FAMILIES)}"
        )
    module, cls = MODEL_FAMILIES[family].split(":")
    return getattr(importlib.import_module(module), cls)


def _known(cls: type, raw: dict):
    known = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in raw.items() if k in known})


def from_json(text: str) -> ExperimentConfig:
    raw = json.loads(text)
    if isinstance(raw.get("model_config"), dict):
        raw["model_config"] = _known(_model_config_class(raw["model_config"]), raw["model_config"])
    if isinstance(raw.get("mesh"), dict):
        raw["mesh"] = _known(MeshConfig, raw["mesh"])
    return _known(ExperimentConfig, raw)


def load_config(name: str) -> ExperimentConfig:
    """Load a named preset from midgpt_tpu.configs (e.g. 'shakespeare_char')."""
    module = importlib.import_module(f"midgpt_tpu.configs.{name}")
    return module.config
