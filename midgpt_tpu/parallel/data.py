"""Host -> device data plumbing for multihost SPMD.

Each host samples its own contiguous shard of the token stream (reference
train.py:122-136) and produces a *process-local* batch; the global jax.Array
is assembled with `jax.make_array_from_process_local_data` — the modern,
TPU-native replacement for the reference's hand-rolled per-device
device_put + make_array_from_single_device_arrays (reference sharding.py:33-42).
"""

from __future__ import annotations

import typing as tp

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from midgpt_tpu.obs import flight_recorder


def make_global_batch(arr: np.ndarray, mesh: Mesh, spec: P) -> jax.Array:
    """Assemble a global array from this process's local slice of the batch.

    `arr` is the process-local chunk: its batch axis is 1/n_proc of the
    global batch. make_array_from_process_local_data infers the global shape
    from the sharding. The host-to-device put is the `data.put` span of the
    flight recorder (one per array: x and y of a step make two).
    """
    with flight_recorder().tracer.span("data.put", "data", "train"):
        sharding = NamedSharding(mesh, spec)
        return jax.make_array_from_process_local_data(sharding, arr)


def replicate(x: tp.Any, mesh: Mesh) -> tp.Any:
    """Fully-replicate host values across the mesh (multihost-safe)."""
    sharding = NamedSharding(mesh, P())

    def put(leaf):
        leaf = np.asarray(leaf)
        return jax.make_array_from_process_local_data(sharding, leaf)

    return jax.tree.map(put, x)
