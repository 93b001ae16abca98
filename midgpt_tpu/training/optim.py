"""Optimizer: AdamW with *independent* (LR-decoupled) weight decay.

Exact reference chain (reference train.py:147-159): global-norm clip 1.0 →
adam moments (b1=0.9, b2 from config) → add params * (weight_decay /
learning_rate) → scale by warmup-cosine schedule → negate. Dividing the decay
by the peak LR before the schedule multiplies makes the *effective* decay
independent of the learning rate (the small-scale-proxies recipe) while still
following the schedule. Decay applies to ALL params of the GPT, including
norm scales and embeddings, as in the reference (`GPT.weight_decay_mask` is
None). A model family whose namespace has a `weight_decay_mask(params)`
(models/kimi_linear.py: False on norm weights, `A_log`, `dt_bias`, the
router's correction bias, the convolution taps) names the leaves that decay.
"""

from __future__ import annotations

import typing as tp

import optax

from midgpt_tpu.config import ExperimentConfig


def make_schedule(config: ExperimentConfig) -> optax.Schedule:
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=config.learning_rate,
        warmup_steps=config.warmup_steps,
        decay_steps=config.lr_decay_steps,
        end_value=config.min_lr,
    )


def make_optimizer(
    config: ExperimentConfig,
) -> tp.Tuple[optax.GradientTransformation, optax.Schedule]:
    schedule = make_schedule(config)
    optimizer = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.scale_by_adam(b2=config.beta2),
        optax.add_decayed_weights(
            config.weight_decay / config.learning_rate, mask=config.model_config.model().weight_decay_mask
        ),
        optax.scale_by_schedule(schedule),
        optax.scale(-1.0),
    )
    return optimizer, schedule


def opt_step_count(opt_state: tp.Any) -> tp.Any:
    """The schedule step from a chain state (reference train.py:150-152 peeks
    opt_state[3].count; here we match the schedule state by type to survive
    chain reorders)."""
    for sub in opt_state:
        if isinstance(sub, optax.ScaleByScheduleState):
            return sub.count
    raise ValueError("no ScaleByScheduleState found in the optimizer chain")
