"""Device mesh construction: a named 5D ('data','fsdp','sp','tp','pp') mesh.

The reference hard-codes Mesh((n_devices // 8, 8), ('replica', 'data')) —
batch over both axes, params over the 8-wide axis (reference train.py:130),
which requires device counts divisible by 8. Here axis sizes come from config
with -1 inference, `mesh_utils.create_device_mesh` picks the physical layout
so 'fsdp' collectives (the per-layer all-gathers/reduce-scatters) ride
contiguous ICI links, 'sp' is the context-parallel axis (ring or Ulysses
attention), 'tp' is the tensor-parallel axis (Megatron column/row sharding
of the block projections, parallel/tp.py), and 'pp' is the pipeline axis
(GPipe stages shard the LAYER dimension, parallel/pipeline.py) — all three
size 1 unless enabled.
"""

from __future__ import annotations

import dataclasses
import typing as tp

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, PartitionSpec as P

from midgpt_tpu.config import MeshConfig

AXES = ("data", "fsdp", "sp", "tp", "pp", "ep")
# The axes token batches shard over (batch_spec below; the shard_map loss
# bodies pmean/fold-in over these).
BATCH_AXES = ("data", "fsdp")


def _axis(size: int) -> int:
    return size if size != -1 else 1


def fit_mesh_config(cfg: MeshConfig, n_devices: int) -> MeshConfig:
    """`cfg` re-derived for a device count it was not written for: the data
    axis inferred, fsdp lowered to its largest divisor of what the other
    axes leave. This is the EXPLICIT topology change of elastic resume
    (training/train.py make_runtime(devices=...)) — make_mesh itself never
    resizes an axis."""
    rest_axes = _axis(cfg.sp) * _axis(cfg.tp) * _axis(cfg.pp) * _axis(cfg.ep)
    if n_devices % rest_axes != 0:
        raise ValueError(
            f"{n_devices} devices not divisible by sp={_axis(cfg.sp)} * "
            f"tp={_axis(cfg.tp)} * pp={_axis(cfg.pp)} * ep={_axis(cfg.ep)}"
        )
    rest = n_devices // rest_axes
    fsdp = max(d for d in range(1, rest + 1) if rest % d == 0 and d <= _axis(cfg.fsdp))
    return dataclasses.replace(cfg, data=-1, fsdp=fsdp)


def make_mesh(
    cfg: tp.Optional[MeshConfig] = None,
    *,
    devices: tp.Optional[tp.Sequence[jax.Device]] = None,
) -> Mesh:
    cfg = cfg or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    fsdp, sp, tp_, pp, ep = (
        _axis(cfg.fsdp), _axis(cfg.sp), _axis(cfg.tp), _axis(cfg.pp), _axis(cfg.ep)
    )
    model_axes = fsdp * sp * tp_ * pp * ep
    if n % model_axes != 0:
        # Never shrink an axis to fit: on a four-chip host a silently
        # clamped fsdp is exactly how "everything on the first chip" hides.
        raise ValueError(
            f"mesh fsdp={fsdp} * sp={sp} * tp={tp_} * pp={pp} * ep={ep} = "
            f"{model_axes} does not divide the {n} device(s) found; set the "
            "mesh for this topology (e.g. --set mesh.fsdp=N)"
        )
    data = cfg.data if cfg.data != -1 else n // model_axes
    if data * model_axes != n:
        raise ValueError(f"mesh {data}x{fsdp}x{sp}x{tp_}x{pp}x{ep} != {n} devices")
    mesh_devices = mesh_utils.create_device_mesh(
        (data, fsdp, sp, tp_, pp, ep), devices=np.asarray(devices)
    )
    return Mesh(mesh_devices, axis_names=AXES)


def batch_spec(with_accum: bool = True, shard_seq: bool = False) -> P:
    """PartitionSpec for token batches.

    (G, B, T) with grad accumulation, (B, T) without. The batch axis shards
    over both 'data' and 'fsdp' (matching the reference's
    P(None, ('replica','data'), None), reference train.py:105); the sequence
    axis shards over 'sp' when context parallelism is on.
    """
    seq = "sp" if shard_seq else None
    spec = (("data", "fsdp"), seq)
    return P(None, *spec) if with_accum else P(*spec)
