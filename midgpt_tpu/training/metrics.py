"""Observability: metrics logging, throughput/MFU accounting, profiler hooks.

Mirrors the reference's surface (wandb + tqdm postfix + jax.profiler,
reference train.py:191-220, launch.py:38-68) but degrades gracefully: wandb
is optional (proc-0 only when present), and every metric always lands in
`rundir/metrics.jsonl` + stdout so headless TPU runs are inspectable.
"""

from __future__ import annotations

import json
import os
import time
import typing as tp

import jax

from midgpt_tpu.config import ExperimentConfig
from midgpt_tpu.models.gpt import GPTConfig
from midgpt_tpu.obs import flight_recorder

try:  # wandb is an optional dependency
    import wandb as _wandb
except Exception:  # pragma: no cover - depends on environment
    _wandb = None


def flops_per_token(cfg, seq_len: tp.Optional[int] = None, stats: tp.Optional[dict] = None) -> float:
    """Training FLOPs/token, by the model family's own count
    (`flops_per_token` of its namespace, models/__init__.py). `stats`: the
    family's counters of a logged step (`route_stats`), where it has any.

    benchmarks/arithmetic*.py hold a copy of each family's count BY DESIGN:
    the yardstick's own, so that a change to the program cannot move a
    reported utilization. tests/test_metrics.py (dense GPT, and the peaks
    table below against benchmarks/peaks.json) and tests/test_kimi_linear.py
    (the hybrid) are what tie the copies to this one."""
    return cfg.model().flops_per_token(cfg, seq_len, stats)


# Peak dense bf16 FLOP/s per chip, keyed by a substring of `device_kind`
# (Google Cloud TPU documentation, per-generation system pages; v5e:
# "TPU v5e", 197 TFLOP/s — jax reports its kind as "TPU v5 lite").
_PEAK_FLOPS = {
    "v6": 918e12,
    "v5p": 459e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 46e12,
}


def device_peak_flops(device: tp.Optional[jax.Device] = None) -> float:
    """Published peak of `device` (default: the first one). A device that
    is not in the table is an error, not a default: a utilization against
    an assumed peak is not a measurement."""
    device = device or jax.devices()[0]
    kind = device.device_kind.lower()
    for name, flops in _PEAK_FLOPS.items():
        if name in kind:
            return flops
    raise ValueError(
        f"no published peak FLOP/s for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to "
        "training/metrics.py _PEAK_FLOPS with its source"
    )


def mfu(tokens_per_sec: float, cfg, n_devices: int, stats: tp.Optional[dict] = None) -> tp.Optional[float]:
    """Model FLOP/s utilization of the attached accelerators; None on the
    host CPU (tests, rehearsals), which has no peak to be utilized against
    — the train loop then logs no MFU at all rather than a made-up one."""
    device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    return tokens_per_sec * flops_per_token(cfg, stats=stats) / (
        device_peak_flops(device) * n_devices
    )


class MetricLogger:
    """jsonl + stdout always; wandb when available (proc 0 only)."""

    def __init__(self, config: ExperimentConfig, *, use_wandb: bool = True, resume_id: tp.Optional[str] = None):
        self.is_main = jax.process_index() == 0
        self.rundir = config.rundir
        self._file = None
        self._wandb = None
        if self.is_main and self.rundir and not self.rundir.startswith("gs://"):
            os.makedirs(self.rundir, exist_ok=True)
            self._file = open(os.path.join(self.rundir, "metrics.jsonl"), "a")
        if self.is_main and use_wandb and _wandb is not None and not config.debug:
            import dataclasses

            if resume_id is None:
                resume_id = self._persistent_run_id()
            self._wandb = _wandb.init(
                project="midgpt-tpu",
                id=resume_id,
                resume="allow",
                config=dataclasses.asdict(config),
            )

    def _persistent_run_id(self) -> tp.Optional[str]:
        """Read or create `rundir/wandb_id.txt` so a relaunched run continues
        the same wandb run (reference launch.py:59-68)."""
        if not self.rundir:
            return None
        path = os.path.join(self.rundir, "wandb_id.txt")
        try:
            if self.rundir.startswith("gs://"):
                import gcsfs

                fs = gcsfs.GCSFileSystem()
                if fs.exists(path):
                    with fs.open(path, "r") as f:
                        return f.read().strip()
                run_id = _wandb.util.generate_id()
                with fs.open(path, "w") as f:
                    f.write(run_id)
                return run_id
            if os.path.exists(path):
                with open(path) as f:
                    return f.read().strip()
            run_id = _wandb.util.generate_id()
            os.makedirs(self.rundir, exist_ok=True)
            with open(path, "w") as f:
                f.write(run_id)
            return run_id
        except Exception:
            return None  # id persistence is best-effort; never block training

    def log(self, step: int, metrics: tp.Dict[str, float]) -> None:
        if not self.is_main:
            return
        record = {"step": step, "time": time.time(), **metrics}
        if self._file is not None:
            self._file.write(json.dumps(record) + "\n")
            self._file.flush()
        if self._wandb is not None:
            self._wandb.log(metrics, step=step)

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
        if self._wandb is not None:
            self._wandb.finish()


class Progress:
    """Live single-line progress bar with a loss/lr/throughput postfix
    (reference train.py:190-220 drives tqdm the same way). Process 0 only,
    and only when stderr is a terminal — headless/nohup runs keep clean
    line-per-interval logs from MetricLogger instead. Degrades to a no-op
    when tqdm is unavailable."""

    def __init__(self, total: int, first_step: int = 0, enabled: bool = True):
        self._bar = None
        if not enabled or jax.process_index() != 0:
            return
        try:
            import sys

            from tqdm import tqdm

            if sys.stderr.isatty():
                self._bar = tqdm(
                    total=total, initial=first_step, dynamic_ncols=True,
                    desc="train", unit="step",
                )
        except Exception:  # pragma: no cover - tqdm is optional
            self._bar = None

    @property
    def active(self) -> bool:
        return self._bar is not None

    def update(self, n: int = 1, **postfix: tp.Any) -> None:
        if self._bar is None:
            return
        if postfix:
            self._bar.set_postfix(postfix, refresh=False)
        self._bar.update(n)

    def close(self) -> None:
        if self._bar is not None:
            self._bar.close()


class Profiler:
    """One-shot trace of the first post-warmup step (reference train.py:205-211)."""

    def __init__(self, rundir: str, enabled: bool):
        self.rundir, self.enabled, self._active = rundir, enabled, False

    def maybe_start(self, step: int, at_step: int = 0) -> None:
        if self.enabled and step == at_step:
            jax.profiler.start_trace(self.rundir or "/tmp/midgpt_trace")
            # One mark on both clocks (what benchmarks/run.py does with
            # `bench.sync`): the annotation lands in the profile, the
            # instant in the flight recorder, and their difference moves
            # the recorder's host spans onto the profile's timeline.
            with jax.profiler.TraceAnnotation("obs.sync"):
                flight_recorder().tracer.instant("obs.sync", "train", "train")
            self._active = True

    def maybe_stop(self, wait_for: tp.Any = None) -> None:
        if self._active:
            if wait_for is not None:
                jax.block_until_ready(wait_for)
            jax.profiler.stop_trace()
            self._active = False
