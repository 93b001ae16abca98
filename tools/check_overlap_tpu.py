"""Assert the ZeRO-3 gather/compute overlap on the REAL TPU backend.

tests/test_shard_map_fsdp.py::test_zero3_gathers_schedulable_ahead_of_compute
pins the dataflow property (weight gathers independent of layer compute) on
the CPU mesh; this tool pins the other half of the claim in
parallel/shard_map_fsdp.py — that the TPU compiler actually exploits that
freedom. The CPU backend emits synchronous all-gathers, so this can only be
shown against the TPU compiler; a v5e:2x4 topology is AOT-compiled for described devices
(no 8-chip hardware needed, no chip at all: the compiler is installed
wherever jax[tpu] is) and the post-optimization HLO is checked structurally. This XLA/libtpu build does
not split async gathers into `all-gather-start`/`-done` instruction pairs in
that text; overlap shows up in two forms, both detected:

  * gathers ANNOTATED `frontend_attributes={async_collective_name=
    "all-gather-start*"}` + a CUSTOM barrier_config (the start/done split
    happens in the backend scheduler), and
  * collective-continuation fusions: block matmul kernels that carry the
    NEXT layer's gather windows as aliased outputs (`continuation_config`,
    `calls=%async_collective_fusion.*`) — the gather is streamed INSIDE the
    compute kernel. The strongest overlap form.

Exit 0 iff EVERY gather-bearing scan body (forward and backward) has at
least one async/fused gather. Run: `JAX_PLATFORMS=cpu python
tools/check_overlap_tpu.py`. Last seen passing on an earlier toolchain; not
re-run on the current one.
"""

from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def build_step_lowered(mesh):
    from midgpt_tpu.config import ExperimentConfig, MeshConfig
    from midgpt_tpu.models.gpt import GPTConfig
    from midgpt_tpu.utils.hlo import lower_abstract_train_step

    config = ExperimentConfig(
        rundir="",
        data_dir="",
        learning_rate=1e-3,
        batch_size=16,
        warmup_steps=2,
        min_lr=1e-4,
        lr_decay_steps=10,
        max_steps=10,
        eval_interval=5,
        beta2=0.95,
        weight_decay=1e-4,
        param_dtype="float32",
        compute_dtype="bfloat16",
        g_accum_iters=1,
        shard_model=True,
        fsdp_min_size=0,
        fsdp_mode="shard_map",
        mesh=MeshConfig(data=1, fsdp=8, sp=1),
        model_config=GPTConfig(
            # Real-ish shapes so the scheduler has matmuls worth hiding
            # gathers behind (tiny dims would be all overhead).
            block_size=512, vocab_size=8192, n_layer=4, n_head=8, n_embd=512,
            attn_impl="naive", scan_unroll=2,
        ),
    )
    return lower_abstract_train_step(config, mesh=mesh)


def analyze(txt: str) -> int:
    """Return 0 iff every gather-bearing scan body overlaps its gathers."""
    from midgpt_tpu.utils.hlo import (
        hlo_computations,
        is_forward_body,
        while_body_names,
    )

    def is_async(l):
        return (
            "all-gather-start(" in l
            or 'async_collective_name="all-gather-start' in l
        )

    bodies = while_body_names(txt)
    bodies_ok, bodies_bad = [], []
    for n, lines in hlo_computations(txt).items():
        # Structural body detection (referenced as body=%n from a while op),
        # not metadata: leaf fusions inherit the body's op_name metadata and
        # must not be graded as bodies — nor may a real body with ONE
        # (combined) serialized gather be skipped.
        if n not in bodies or not any("shard_map/while" in l for l in lines):
            continue
        n_sync = sum(
            1 for l in lines if " all-gather(" in l and not is_async(l)
        )
        n_annot = sum(1 for l in lines if is_async(l))
        cont_lines = [l for l in lines if "calls=%async_collective_fusion" in l]
        # op_name labels feed the DISPLAY only — the count must not depend
        # on metadata naming (it drifts across XLA versions).
        cont_ops = [
            m.group(1)
            for m in (
                re.search(r'op_name="[^"]*?/(block/[\w,>-]+(?:/[\w,>-]+)?)', l)
                for l in cont_lines
            )
            if m
        ]
        if n_sync + n_annot + len(cont_lines) == 0:
            continue  # gather-free body (not a ZeRO-3 layer scan)
        kind = "forward" if is_forward_body(lines) else "backward"
        print(
            f"{kind} scan body {n}: {n_annot} annotated-async gathers, "
            f"{len(cont_lines)} gathers fused into compute kernels "
            f"(continuation fusions on: {sorted(set(cont_ops))}), "
            f"{n_sync} plain"
        )
        (bodies_ok if n_annot + len(cont_lines) > 0 else bodies_bad).append(
            (kind, n)
        )
    if not bodies_ok and not bodies_bad:
        print("FAIL: no gather-bearing scan body found — did lowering change?")
        return 1
    if bodies_bad:
        print(
            "FAIL: scan bodies with fully-serialized gathers: "
            f"{bodies_bad} — the ZeRO-3 weight stream there runs behind "
            "compute instead of overlapping it"
        )
        return 1
    print(
        f"OK: the ZeRO-3 weight stream overlaps compute in all "
        f"{len(bodies_ok)} gather-bearing scan bodies {bodies_ok} — via "
        "async annotation and collective-continuation fusion into the "
        "block matmul kernels"
    )
    return 0


def main() -> int:
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from midgpt_tpu.parallel.mesh import AXES

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x4")
    mesh = Mesh(
        np.asarray(topo.devices).reshape(1, 8, 1, 1, 1, 1), axis_names=AXES
    )
    lowered = build_step_lowered(mesh)
    # NOT default-on in this toolchain's compile path (measured: without the
    # flag, zero gathers are async-ified). Real-pod launches must set it —
    # see docs/PARALLELISM.md "Overlap".
    opts = {"xla_tpu_enable_latency_hiding_scheduler": "true"}
    txt = lowered.compile(compiler_options=opts).as_text()
    return analyze(txt)


if __name__ == "__main__":
    sys.exit(main())
