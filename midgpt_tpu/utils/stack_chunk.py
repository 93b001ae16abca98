"""Call a function so that everything it calls shares ONE chunk of the
interpreter's frame stack.

CPython 3.11+ keeps a thread's Python frames in chunks of 16 KiB
(`Python/pystate.c` `push_chunk`), maps a new chunk when a frame does not fit
in the current one, and hands that chunk back to the kernel the moment the
first frame in it returns (`_PyThreadState_PopFrame`); only a thread's very
first chunk is kept. A call made again and again from a frame that ends just
short of a chunk's end therefore maps and unmaps 16 KiB every time: 7 us a
call where a call inside a chunk is 40 ns (this sandbox; `tests/
test_stack_chunk.py` shows the spike every ~136 small frames), and far more
where the kernel's part is slow.

Tracing and lowering one serving program is ~80 frames and ~40 KiB deep and
makes 10^5 calls from a handful of depths (jax's `jaxpr_subcomp` ->
`_cached_lowering` -> ...), so it crosses two or three chunk ends, and WHICH
of its calls straddle one turns on the byte size of every frame under the
jit call. On the benchmark's machines that decided whether a `mimo_v2` decode
program lowered in 0.45 s or in 1.8 s: one local more in `ServeEngine.step`,
or the script run through `runpy`, moved `serve_mimo_v2_5_mixed`'s set-up by
30-70 s with the same programs, event for event (PERF.md §6, PR 32).

`call_on_own_chunk(f, ...)` calls `f` from a frame that DECLARES an operand
stack of `SLOTS` entries it never uses. The interpreter sizes a fresh chunk
to hold that frame (`push_chunk` doubles 16 KiB until it fits: 512 KiB here),
and what `f` calls goes behind it in the same chunk, 256 KiB of room, whatever
lay under the call. The unused entries are never written, so the mapping
costs address space and the pages `f`'s frames touch, and the call itself two
system calls: use it around work that compiles, not around a dispatch.
"""

from __future__ import annotations

import typing as tp

# operand-stack entries the trampoline's frame declares: 32 Ki x 8 bytes =
# 256 KiB, six times what tracing + lowering a serving program stacks up
SLOTS = 1 << 15

_trampoline: tp.Optional[tp.Callable] = None


def _build(slots: int) -> tp.Callable:
    # A lambda's defaults are pushed one by one before the function object is
    # made, and the compiler does not spill them into a list as it does a long
    # tuple or call: `slots` defaults make `co_stacksize >= slots`. The branch
    # never runs; the frame is sized for it all the same.
    defaults = ",".join(f"a{i}=f" for i in range(slots))
    src = (
        "def call_on_own_chunk(f, *args, **kwargs):\n"
        "    if f is None:\n"
        f"        return lambda {defaults}: None\n"
        "    return f(*args, **kwargs)\n"
    )
    scope: tp.Dict[str, tp.Any] = {}
    exec(compile(src, "<midgpt_tpu.utils.stack_chunk>", "exec"), scope)
    return scope["call_on_own_chunk"]


def call_on_own_chunk(f: tp.Callable, *args, **kwargs):
    """`f(*args, **kwargs)`, its callees' frames in one chunk of their own
    (module docstring). Built on first use: compiling the trampoline's
    32 Ki-parameter lambda takes ~0.15 s, once a process."""
    global _trampoline
    if _trampoline is None:
        _trampoline = _build(SLOTS)
    return _trampoline(f, *args, **kwargs)
