"""The held experts' SwiGLU over rows SORTED BY EXPERT, one Pallas call (TPU
serving path; `ops/moe.py` `moe_experts_serving` lays the rows out and sums
the result back).

The rows come in blocks of `block_rows`, each block ONE expert's (its run
padded to whole blocks with zero rows), the blocks in use first. Grid = (row
blocks, slices of F). Each block's expert rides scalar prefetch, and the three
weight BlockSpecs' index maps read it: the pipeline Pallas builds around the
body therefore has block i+1's expert in flight while block i is multiplied,
and copies nothing when the block index repeats. An expert whose run is one
block is streamed ONCE a call; so is an expert whose run spans several blocks
when F is not sliced (with slices the run's second block starts again at slice
0, and the expert is streamed once a block). Blocks past those in use repeat
the last used block's indices (rows, expert, its LAST slice, and result alike:
no copy; a slice index that kept running would stream an expert a dead block,
which is most of a buffer sized for the worst case) and skip their compute;
their rows of the result are never written and must not be read.

Per block and slice f: h = SiLU(xs W_gate[e, f]^T) * (xs W_up[e, f]^T), float32
out of operands of the weights' type; ys += h W_down[e, :, f]^T, float32, in
the result's own block (resident while f runs); each slice's product is
weighted by the rows' pair weights `ws` (float32, 0 on padding) before it is
added. Nothing is narrower than `ops/moe.py` `swiglu` over the same operands.

What a grid step holds in VMEM is two copies (the pipeline's) of three weight
slices (f_slice, D), of the row block in and of the float32 row block out, and
the step's float32 intermediates: `f_slice` takes the widest slice of F (F
halved while it stays whole lanes) that keeps them inside `VMEM_BLOCKS`, from
D, F, the row block and the itemsize alone. `VMEM_LIMIT` is what Mosaic is
given (`vmem_limit_bytes`), and it is SMALL on purpose: XLA sets the whole of
it aside, in EVERY program that holds the call and for all of that program,
out of the 128 MiB it otherwise uses to stage the operands of its own fusions
(at 96 MiB the Pangu cell's attention slowed by a third and the cell by 7 %;
at 48 and at 32 MiB by 4 %: PERF.md section 6 PR 50). 32 MiB is the least that
holds a whole Trinity expert twice over for a decode step's row block, where a
slice costs the most (8 % of the kernel); the slice width moves the wider
families' kernels by 1-4 %.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from midgpt_tpu.kernels.flash_attention import _interpret

Array = jax.Array
VMEM_LIMIT = 32 * 2**20  # Mosaic's, through the compiler params; XLA sets ALL of it aside (module docstring)
VMEM_BLOCKS = 26 * 2**20  # of it, the pipeline's blocks and the step's intermediates


def f_slice(block_rows: int, D: int, F: int, itemsize: int) -> int:
    """Columns of F one grid step multiplies (module docstring)."""
    rows = 2 * block_rows * D * (itemsize + 4)  # the row block in and out, twice each
    bf = F
    while bf % 256 == 0 and 6 * bf * D * itemsize + rows + 3 * block_rows * bf * 4 > VMEM_BLOCKS:
        bf //= 2
    return bf


def _kernel(expert_ref, used_ref, xs_ref, ws_ref, wg_ref, wu_ref, wd_ref, ys_ref, *, n_slices: int):
    del expert_ref  # index maps only
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when(i < used_ref[0])
    def _():
        ct = jnp.promote_types(xs_ref.dtype, wg_ref.dtype)
        dot = lambda a, w: jax.lax.dot_general(  # a (m, c) . w (n, c)^T, float32 out
            a.astype(ct), w.astype(ct), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        x = xs_ref[...]
        h = jax.nn.silu(dot(x, wg_ref[...])) * dot(x, wu_ref[...])
        y = dot(h, wd_ref[...]) * ws_ref[...]
        if n_slices == 1:
            ys_ref[...] = y
        else:
            @pl.when(f == 0)
            def _():
                ys_ref[...] = y

            @pl.when(f > 0)
            def _():
                ys_ref[...] += y


# jit: every routed layer of a program makes this call with the same shapes,
# so it is traced and lowered for Mosaic once a program (kernels/paged_write.py).
@functools.partial(jax.jit, static_argnames=("block_rows",))
def grouped_swiglu(
    xs: Array,  # (P, D) rows sorted by expert, P a multiple of `block_rows`
    ws: Array,  # (P,) f32 pair weight of each row, 0 on padding
    block_expert: Array,  # (P / block_rows,) int32: each block's expert; past `blocks_used`: the last used one
    blocks_used: Array,  # () int32
    w_gate: Array,  # (E, F, D)
    w_up: Array,  # (E, F, D)
    w_down: Array,  # (E, D, F)
    *, block_rows: int,
) -> Array:
    """ys (P, D) f32: row p = ws[p] * SwiGLU_{block_expert[p // block_rows]}(xs[p])
    for the rows of the first `blocks_used` blocks; the other rows are not written."""
    P, D = xs.shape
    F = w_gate.shape[1]
    bm, n_blocks = block_rows, P // block_rows
    bf = f_slice(bm, D, F, w_gate.dtype.itemsize)
    n_slices = F // bf

    def row(i, f, expert, used):  # a block past those in use: the last used block again
        return jnp.minimum(i, jnp.maximum(used[0] - 1, 0)), 0

    def weight(i, f, expert, used):  # ... and the last slice of its expert again: nothing is fetched for it
        return expert[i], jnp.where(i < used[0], f, n_slices - 1)

    rows_in = pl.BlockSpec((bm, D), row)
    weight_in = pl.BlockSpec((None, bf, D), lambda *a: (*weight(*a), 0))
    return pl.pallas_call(
        functools.partial(_kernel, n_slices=n_slices),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_blocks, n_slices),
            in_specs=[
                rows_in,
                pl.BlockSpec((bm, 1), row),
                weight_in,
                weight_in,
                pl.BlockSpec((None, D, bf), lambda *a: (weight(*a)[0], 0, weight(*a)[1])),
            ],
            out_specs=pl.BlockSpec((bm, D), row),
        ),
        out_shape=jax.ShapeDtypeStruct((P, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT),
        interpret=_interpret(),
    )(block_expert, jnp.asarray(blocks_used, jnp.int32).reshape(1), xs, ws[:, None], w_gate, w_up, w_down)
