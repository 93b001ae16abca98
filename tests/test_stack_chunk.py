"""utils/stack_chunk.py: a call made from the end of a frame-stack chunk maps
and unmaps a chunk every time; under `call_on_own_chunk` no depth does."""

import sys
import time

import pytest

from midgpt_tpu.utils import stack_chunk


def _leaf(a, b):
    return a


def _hot(n):
    t = time.perf_counter()
    for _ in range(n):
        _leaf(1, 2)
    return (time.perf_counter() - t) / n


def _at_depth(d, n):
    return _hot(n) if d == 0 else _at_depth(d - 1, n)


def _sweep(call, depths, n=2000, repeats=3):
    """ns a leaf call at each recursion depth: the least of `repeats`, so that a
    busy machine's hiccup is not read as a chunk end."""
    return [min(call(_at_depth, d, n) for _ in range(repeats)) * 1e9 for d in depths]


def test_the_trampolines_frame_declares_its_slots_and_passes_everything_through():
    def f(x, *, y=0):
        if x < 0:
            raise ValueError("negative")
        return x + y

    assert stack_chunk.call_on_own_chunk(f, 2, y=3) == 5
    with pytest.raises(ValueError, match="negative"):
        stack_chunk.call_on_own_chunk(f, -1)
    code = stack_chunk._trampoline.__code__
    assert code.co_stacksize >= stack_chunk.SLOTS and code.co_nlocals == 3
    assert stack_chunk._build(64).__code__.co_stacksize >= 64


def test_no_depth_under_the_trampoline_pays_for_a_chunk_a_call():
    depths = range(0, 420)  # three 16 KiB chunk ends at ~136 small frames a chunk
    old = sys.getrecursionlimit()
    sys.setrecursionlimit(max(old, 2000))
    try:
        plain = _sweep(lambda f, *a: f(*a), depths)
        own = _sweep(stack_chunk.call_on_own_chunk, depths, repeats=5)
    finally:
        sys.setrecursionlimit(old)
    base = sorted(plain)[len(plain) // 2]
    if max(plain) < 20 * base:
        pytest.skip(f"this interpreter shows no chunk end in {len(plain)} depths "
                    f"(median {base:.0f} ns, worst {max(plain):.0f} ns): nothing to guard against")
    # a chunk end costs ~190 medians here and ~2,000 on the benchmark's machines
    assert max(own) < 20 * sorted(own)[len(own) // 2], (max(own), sorted(own)[len(own) // 2])
