"""The serving experts (`ops/moe.py` `moe_experts_serving`): the plan that
sorts a call's pairs by held expert, the grouped matmul over its row blocks
(`kernels/grouped_matmul.py`, in interpret mode here) and the counters they
feed, at toy widths against the every-expert dense answer."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.ops import moe

gm = importlib.import_module("midgpt_tpu.kernels.grouped_matmul")
D, F = 32, 24


def _weights(n_held, dtype=jnp.float32, d=D, f=F):
    key = jax.random.PRNGKey(7)
    return tuple((jax.random.normal(jax.random.fold_in(key, i), s) / 6).astype(dtype)
                 for i, s in enumerate(((n_held, f, d), (n_held, f, d), (n_held, d, f))))


def _dense(x, idx, w, wg, wu, wd, offset):
    """Every held expert over every row in float32, masked by the pair weights."""
    f32 = lambda a: a.astype(jnp.float32)
    w_tok = jnp.sum(jnp.where((idx - offset)[..., None] == jnp.arange(wg.shape[0]), w[..., None], 0.0), axis=1)
    out = jax.vmap(lambda g, u, d: moe.swiglu(f32(x), f32(g), f32(u), f32(d)))(wg, wu, wd)
    return jnp.einsum("end,ne->nd", out, w_tok)


def _routed(rows, n_experts, top_k, dtype=jnp.float32, same_from=None):
    """rows x (rows, D) and their route; from row `same_from` on every row is
    the same vector (what the slots no request holds feed a decode step)."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (rows, D))
    if same_from is not None:
        x = x.at[same_from:].set(x[same_from])
    idx, w = moe.route(x, jax.random.normal(jax.random.fold_in(key, 1), (n_experts, D)),
                       jnp.zeros((n_experts,)), top_k=top_k, scale=1.0)
    return x.astype(dtype), idx, w


CASES = {
    # name: rows, experts, held, offset, top_k, dtype, what else
    "decode_64_rows_top8_of_128": (64, 128, 128, 0, 8, jnp.float32, None),
    "chunk_of_512_rows": (512, 128, 128, 0, 8, jnp.float32, None),
    "rows_no_multiple_of_the_block": (37, 16, 8, 4, 4, jnp.float32, None),
    "one_expert_takes_every_pair": (100, 64, 4, 8, 4, jnp.float32, "one_expert"),
    "rows_of_inactive_slots": (64, 128, 128, 0, 8, jnp.float32, "inactive"),
    "bf16_decode": (64, 128, 128, 0, 8, jnp.bfloat16, None),
    "bf16_chunk": (512, 128, 128, 0, 8, jnp.bfloat16, None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_serving_experts_give_the_dense_answer(case):
    """`moe_serving`'s experts against every held expert over every row, at the
    row block the shapes give: decode rows, a chunk, rows that fill no whole
    block, a run of several blocks (visits > experts touched), rows that are
    all one vector, float32 at the present test's tolerance and bf16 at its
    rounding. Nothing is dropped and the visits are the blocks the counts need."""
    rows, n_experts, n_held, offset, top_k, dtype, what = CASES[case]
    x, idx, w = _routed(rows, n_experts, top_k, dtype, same_from=40 if what == "inactive" else None)
    if what == "one_expert":  # every token's first pair goes to held expert 2, the others off this chip
        idx = jnp.full_like(idx, 0).at[:, 0].set(offset + 2)
    wg, wu, wd = _weights(n_held, dtype)
    block = moe.moe_row_block(rows, top_k, n_experts, jnp.dtype(dtype).itemsize)
    y, stats = jax.jit(lambda *a: moe.moe_experts_serving(*a, offset=offset, block_rows=block))(x, idx, w, wg, wu, wd)
    want = _dense(x, idx, w, wg, wu, wd, offset)
    tol = dict(atol=2e-5) if dtype == jnp.float32 else dict(atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(want), **tol)
    counts = np.asarray(stats["counts"])
    local = np.asarray(idx) - offset
    np.testing.assert_array_equal(counts, [(local == e).sum() for e in range(n_held)])
    assert int(stats["dropped"]) == 0 and y.dtype == x.dtype
    assert int(stats["visits"]) == int(np.sum(-(-counts // block)))
    if what == "one_expert":
        assert counts.tolist() == [0, 0, rows, 0] and int(stats["visits"]) == -(-rows // block) > 1
    if what == "inactive":  # 24 rows pick the same 8 experts: their runs still fit a block, or take a second one
        assert counts.max() >= 24


@pytest.mark.parametrize("pattern", ["routed", "one_expert", "none_held", "one_pair_an_expert", "every_pair_held"])
def test_the_plan_gives_every_held_pair_one_row_in_a_block_of_its_expert(pattern):
    """`moe_serving_plan`'s invariants, whatever `idx`: every held pair has
    exactly one row, that row names its token and carries its weight, a block
    holds one expert, padding rows carry weight 0 and no token, the blocks past
    those in use repeat the last used expert, and the buffer never overflows."""
    rows, top_k, n_held, offset, block = 24, 4, 6, 2, 8
    _, idx, w = _routed(rows, 16, top_k)
    idx = np.array(idx)
    if pattern == "one_expert":
        idx[:] = 0
        idx[:, 1] = offset + 5
    elif pattern == "none_held":
        idx[:] = np.where(idx >= offset, idx + n_held, idx) % 16
        idx[(idx >= offset) & (idx < offset + n_held)] = 0
    elif pattern == "one_pair_an_expert":
        idx[:] = 0
        idx[:n_held, 0] = offset + np.arange(n_held)
    elif pattern == "every_pair_held":  # the buffer's worst case is every pair on this chip, runs ending inside a block
        rng = np.random.default_rng(0)
        idx[:] = offset + np.stack([rng.permutation(n_held)[:top_k] for _ in range(rows)])
    plan = jax.jit(lambda i, ww: moe.moe_serving_plan(i, ww, offset=offset, n_held=n_held, block_rows=block))(
        jnp.asarray(idx, jnp.int32), w)
    plan = jax.tree.map(np.asarray, plan)
    P = (-(-rows * top_k // block) + n_held) * block
    assert plan["src"].shape == plan["ws"].shape == (P,) and plan["block_expert"].shape == (P // block,)
    local = idx - offset
    held = (local >= 0) & (local < n_held)
    counts = np.array([(local[held] == e).sum() for e in range(n_held)])
    np.testing.assert_array_equal(plan["counts"], counts)
    used = int(plan["blocks_used"])
    assert used == np.sum(-(-counts // block)) <= P // block
    taken = plan["row"][held]
    assert len(set(taken.tolist())) == len(taken) and (taken < used * block).all()  # one row a pair, inside the blocks in use
    assert (plan["row"][~held] == P).all()  # no row: reads as zeros
    n_of, _ = np.nonzero(held)
    np.testing.assert_array_equal(plan["src"][taken], n_of)
    np.testing.assert_allclose(plan["ws"][taken], np.asarray(w)[held])
    np.testing.assert_array_equal(plan["block_expert"][taken // block], local[held])  # a block holds ONE expert
    padding = np.ones(P, bool)
    padding[taken] = False
    assert (plan["ws"][padding] == 0).all() and (plan["src"][padding] == rows).all()
    if used:
        assert (plan["block_expert"][used:] == plan["block_expert"][used - 1]).all()
        runs = plan["block_expert"][:used]
        assert (np.diff(runs) >= 0).all()  # sorted by expert: an expert's blocks are consecutive


def _xla_grouped_swiglu(xs, ws, block_expert, used, wg, wu, wd, block):
    """The kernel's contract in plain XLA: block b's rows through expert
    block_expert[b], weighted; blocks past `used` zeroed (the kernel leaves them unwritten)."""
    xb = xs.reshape(-1, block, xs.shape[-1])
    yb = jax.vmap(lambda x, e: moe.swiglu(x, wg[e], wu[e], wd[e]))(xb, block_expert).astype(jnp.float32)
    yb = yb * ws.reshape(-1, block, 1) * (jnp.arange(xb.shape[0]) < used)[:, None, None]
    return yb.reshape(xs.shape[0], -1)


@pytest.mark.parametrize("slices", [1, 4])
def test_the_kernel_is_its_xla_formulation(slices, monkeypatch):
    """`grouped_swiglu` in interpret mode against the same blocks in XLA at a
    toy width, whole F a grid step and F in four slices (the result's block
    accumulates over them), the unused blocks skipped."""
    d, f, block, n_held = 128, 512, 8, 5
    if slices > 1:  # a budget that leaves room for a quarter of F: what the published widths do to the real one
        monkeypatch.setattr(gm, "VMEM_BLOCKS", 6 * (f // slices) * d * 4 + 2 * block * d * 8 + 3 * block * (f // slices) * 4)
    assert gm.f_slice(block, d, f, 4) == f // slices
    wg, wu, wd = _weights(n_held, d=d, f=f)
    key = jax.random.PRNGKey(slices)
    xs = jax.random.normal(key, (9 * block, d))
    ws = jax.random.uniform(jax.random.fold_in(key, 1), (9 * block,))
    block_expert = jnp.asarray([0, 0, 1, 3, 3, 3, 4, 4, 4], jnp.int32)  # the last two: past those in use
    got = gm.grouped_swiglu(xs, ws, block_expert, jnp.asarray(7), wg, wu, wd, block_rows=block)
    want = _xla_grouped_swiglu(xs, ws, block_expert, 7, wg, wu, wd, block)
    np.testing.assert_allclose(np.asarray(got)[: 7 * block], np.asarray(want)[: 7 * block], atol=2e-5)


def test_f_slice_reads_the_published_widths():
    """The F slice comes from D, F, the row block and the itemsize alone (bf16,
    the row blocks the families' decode steps and chunks take): a whole Trinity
    expert a grid step of a decode step and half of one in a chunk (its row
    blocks are eight times as tall), a quarter / an eighth of a MiMo expert, an
    eighth / a sixteenth of a Pangu one: whole lanes, dividing F, inside the budget."""
    assert [gm.f_slice(b, 2048, 1024, 2) for b in (16, 128)] == [1024, 512]
    assert [gm.f_slice(b, 4096, 2048, 2) for b in (16, 64)] == [512, 256]
    assert [gm.f_slice(b, 7680, 2048, 2) for b in (16, 64)] == [256, 128]
    assert gm.f_slice(8, D, F, 4) == F  # a toy width is never sliced


def test_the_counter_reads_blocks_in_use():
    """`moe.expert_visits` is the row blocks in use a decode step a layer:
    the experts touched when every run fits its block and every slot is
    active, more when one expert's run takes several blocks."""
    rows, n_experts, n_held, top_k = 16, 16, 8, 4
    x, idx, w = _routed(rows, n_experts, top_k)
    wg, wu, wd = _weights(n_held)
    active = jnp.ones((rows,), bool)

    def step(idx, block):
        _, stats = moe.moe_experts_serving(x, idx, w, wg, wu, wd, offset=0, block_rows=block)
        counts, totals = moe.moe_counters_init(1, n_held)
        counts, totals = moe.moe_count_decode(counts, totals, 0, idx, active, stats, offset=0)
        return moe.moe_serve_counters(counts, totals.at[0].add(1)), stats

    got, stats = step(idx, 16)  # 16 rows: no run can pass a block of 16
    assert got["moe.expert_visits"] == int(stats["visits"]) == got["moe.experts_touched"] > 0
    assert got["moe.dropped"] == 0 and got["moe.decode_steps"] == 1
    one = jnp.asarray([3, 12, 13, 14], jnp.int32) * jnp.ones_like(idx)  # held expert 3 takes 16 pairs: two blocks of 8
    got, stats = step(one, 8)
    assert got["moe.experts_touched"] == 1 and got["moe.expert_visits"] == int(stats["visits"]) == 2
    totals = moe.moe_count_dropped(jnp.zeros((4,), jnp.int32), jnp.asarray(2))
    assert totals.tolist() == [0, 0, 2, 0]  # a prefill chunk counts what it dropped, nothing else


@pytest.mark.parametrize("case", ["a_chunks_padded_tail", "empty_places_between_rows", "no_row_is_a_token"])
def test_rows_that_are_no_tokens_take_no_place_among_the_experts(case):
    """`moe_serving(valid=...)` (a batched prefill call's rows past `n_valid`
    and its empty places): the valid rows get what the call over them ALONE
    gives, the others zeros and `idx` -1; no pair of theirs is in an expert's
    count, nothing is dropped, and the blocks in use are the blocks the valid
    rows' counts need, at the row block of the WHOLE call's shape."""
    rows, n_experts, n_held, offset, top_k = 48, 16, 8, 4, 4
    valid = {"a_chunks_padded_tail": np.arange(rows) < 29,
             "empty_places_between_rows": (np.arange(rows) // 12) % 2 == 0,
             "no_row_is_a_token": np.zeros(rows, bool)}[case]
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (rows, D))
    router, bias = jax.random.normal(jax.random.fold_in(key, 1), (n_experts, D)), jnp.zeros((n_experts,))
    wg, wu, wd = _weights(n_held)
    serve = lambda xx, vv: moe.moe_serving(xx, router, bias, wg, wu, wd, top_k=top_k, scale=1.5, renormalize=True,
                                           offset=offset, valid=vv)
    y, idx, stats = jax.jit(serve)(x, jnp.asarray(valid))
    y, idx = np.asarray(y), np.asarray(idx)
    assert (idx[~valid] == -1).all() and not y[~valid].any() and int(stats["dropped"]) == 0
    block = moe.moe_row_block(rows, top_k, n_experts, 4)
    if valid.any():
        y_alone, idx_alone, alone = serve(x[valid], None)
        np.testing.assert_allclose(y[valid], np.asarray(y_alone), atol=2e-5)
        np.testing.assert_array_equal(idx[valid], np.asarray(idx_alone))
        np.testing.assert_array_equal(np.asarray(stats["counts"]), np.asarray(alone["counts"]))
    counts = np.asarray(stats["counts"])
    assert counts.sum() == ((idx >= offset) & (idx < offset + n_held)).sum()
    assert int(stats["visits"]) == int(np.sum(-(-counts // block))) and (valid.any() or int(stats["visits"]) == 0)
