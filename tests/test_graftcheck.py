"""graftcheck pass-1 lint + pass-3 lifecycle + pass-4 concurrency: one
deliberate-violation fixture per rule (GC001-GC011, GC013-GC016;
path-scoped GC012 gets dedicated tests below — it cannot live in FIXTURES
because it only fires under `sampling/` / `robustness/` paths),
suppression semantics, the jit-surface census/diff, and the CLI contract
(nonzero exit with rule ID + file:line on violations; --json is one
schema-conformant line; --fail-on-new gates on the committed baselines).
The repo-wide "tree is clean" gate lives in tests/test_lint_clean.py.
"""

import json
import os
import subprocess
import sys

import pytest

from midgpt_tpu.analysis.bench_contract import (
    check_bench_stdout,
    check_graftcheck,
    parse_single_json_line,
)
from midgpt_tpu.analysis.concurrency import concurrency_source
from midgpt_tpu.analysis.jit_surface import diff_surface, jit_surface
from midgpt_tpu.analysis.lifecycle import lifecycle_source
from midgpt_tpu.analysis.lint import lint_source, parse_suppressions


def check_source(src, path):
    """All three JAX-free passes merged — every fixture must trip exactly
    its own rule and stay clean under the other passes."""
    active, suppressed = lint_source(src, path)
    a3, s3 = lifecycle_source(src, path)
    a4, s4 = concurrency_source(src, path)
    merged = sorted(active + a3 + a4, key=lambda f: (f.line, f.col, f.rule))
    return merged, suppressed + s3 + s4

# One minimal violating snippet per rule; (rule, expected line) is asserted
# exactly so a rule that silently stops firing fails loudly here.
FIXTURES = {
    "GC001": (
        """\
import jax
from jax.experimental import pallas as pl

def _kern(x_ref, o_ref):
    o_ref[0] = jax.lax.cond(x_ref[0] > 0, lambda: x_ref[0], lambda: x_ref[1])

def run(x):
    return pl.pallas_call(_kern, out_shape=x)(x)
""",
        5,
    ),
    "GC002": (
        """\
import jax

@jax.jit
def f(x):
    return float(x) + 1.0
""",
        5,
    ),
    "GC003": (
        """\
from jax.experimental import pallas as pl

spec = pl.BlockSpec((4, 100), lambda i: (i, 0))
""",
        3,
    ),
    "GC004": (
        """\
import functools

import jax

@functools.partial(jax.jit, donate_argnums=(0,))
def f(buf, x):
    return buf + x

def run(buf, x):
    y = f(buf, x)
    return y + buf.sum()
""",
        11,
    ),
    "GC005": (
        """\
import time

import jax

@jax.jit
def f(x):
    return x + time.time()
""",
        7,
    ),
    "GC006": (
        """\
def attn(q):
    \"\"\"Numerical parity with the fused path is exact.\"\"\"
    return q
""",
        1,
    ),
    "GC007": (
        """\
def flush(mngr, state):
    try:
        mngr.save(0, state)
    except Exception:
        pass
""",
        4,
    ),
    "GC008": (
        """\
import jax.numpy as jnp

def quantize(x, scale):
    return (x / scale).astype(jnp.int8)
""",
        4,
    ),
    # exception-edge leak: pages acquired, then a raise with no cleanup
    "GC009": (
        """\
def handoff(allocator, n):
    pages = allocator.alloc(n)
    if pages is None:
        return None
    if n > 8:
        raise ValueError(n)
    allocator.free(pages)
    return n
""",
        6,
    ),
    # await interleaved inside a mutation-in-progress region
    "GC010": (
        """\
import asyncio

class Server:
    async def rotate(self, item):
        self.slots = []
        await asyncio.sleep(0)
        self.slots = [item]
""",
        6,
    ),
    # unbounded request-derived value at a static jit position
    "GC011": (
        """\
import functools

import jax

@functools.partial(jax.jit, static_argnums=(1,))
def step(x, n):
    return x * n

def drive(x, requests):
    for r in requests:
        x = step(x, r)
    return x
""",
        11,
    ),
    # thread-escape mutation of engine-owned state
    "GC013": (
        """\
import threading

class Serve:
    def start(self):
        threading.Thread(target=self._worker, daemon=True).start()

    def _worker(self):
        self.engine.temperature = 0.0
""",
        8,
    ),
    # allocating (IO-performing) signal handler
    "GC014": (
        """\
import signal

def _on_term(signum, frame):
    with open("/tmp/flag", "w") as fh:
        fh.write("x")

def install():
    signal.signal(signal.SIGTERM, _on_term)
""",
        4,
    ),
    # a lock riding a handoff payload
    "GC015": (
        """\
class Disagg:
    def enqueue(self, uid):
        item = HandoffItem(uid=uid, lock=self._lock)
        self.handoff_queue.push(item)
""",
        3,
    ),
    # structured error raised without its declared fields
    "GC016": (
        """\
def give_up(step):
    raise CheckpointWriteError(f"save at {step} failed")
""",
        2,
    ),
}


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_each_rule_fires_on_its_fixture(rule):
    src, line = FIXTURES[rule]
    active, suppressed = check_source(src, f"{rule}.py")
    assert [(f.rule, f.line) for f in active] == [(rule, line)], active
    assert not suppressed


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_each_rule_suppressible_inline(rule):
    src, line = FIXTURES[rule]
    lines = src.splitlines()
    lines[line - 1] += f"  # graftcheck: disable={rule} — fixture: rule under test"
    active, suppressed = check_source("\n".join(lines) + "\n", f"{rule}.py")
    assert active == []
    assert [(f.rule, f.line) for f in suppressed] == [(rule, line)]


def test_suppression_justification_is_captured():
    src = "x = 1  # graftcheck: disable=GC003 — spans the full array dim\n"
    (s,) = parse_suppressions(src)
    assert s.rules == ("GC003",) and s.line == 1
    assert "full array dim" in s.justification


def test_clean_code_with_traced_scopes_passes():
    src = """\
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    n = int(x.shape[0])  # static shape math is not a host sync
    return x * n + float("-inf")
"""
    active, _ = lint_source(src, "clean.py")
    assert active == []


def test_gc004_accepts_rebinding_and_flags_loop_reuse():
    ok = """\
import functools

import jax

@functools.partial(jax.jit, donate_argnums=(0,))
def f(buf, x):
    return buf + x

def run(buf, xs):
    for x in xs:
        buf = f(buf, x)
    return buf
"""
    active, _ = lint_source(ok, "ok.py")
    assert active == []
    bad = ok.replace("        buf = f(buf, x)", "        out = f(buf, x)").replace(
        "    return buf\n", "    return out\n"
    )
    active, _ = lint_source(bad, "bad.py")
    assert [f.rule for f in active] == ["GC004"]


def test_gc008_accepts_rounded_cast_and_string_dtype():
    """The blessed quantization shape — round (possibly under clip) before
    the int8 cast — passes; a truncating cast via the STRING dtype
    spelling is still caught."""
    ok = """\
import jax.numpy as jnp

def quantize(x, scale):
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
"""
    active, _ = lint_source(ok, "ok.py")
    assert active == []
    bad = 'def f(x):\n    return x.astype("int8")\n'
    active, _ = lint_source(bad, "bad.py")
    assert [(f.rule, f.line) for f in active] == [("GC008", 2)]
    # clip alone is NOT rounding evidence (it still truncates)
    clip_only = """\
import jax.numpy as jnp

def f(x):
    return jnp.clip(x, -127, 127).astype(jnp.int8)
"""
    active, _ = lint_source(clip_only, "clip.py")
    assert [f.rule for f in active] == ["GC008"]


def test_gc006_accepts_reference_or_test_citation():
    for cite in ("reference model.py:76", "tests/test_flash.py"):
        src = f'def f(q):\n    """Parity pinned ({cite})."""\n    return q\n'
        active, _ = lint_source(src, "cited.py")
        assert active == [], cite


def test_gc012_bare_clock_call_fires_only_in_scope():
    """Path-scoped: a bare clock CALL flags under sampling/ and
    robustness/ components, and nowhere else."""
    src = """\
import time

class Engine:
    def step(self):
        t0 = time.perf_counter()
        return t0
"""
    active, _ = check_source(src, "midgpt_tpu/sampling/serve.py")
    assert [(f.rule, f.line) for f in active] == [("GC012", 5)]
    active, _ = check_source(
        src.replace("perf_counter", "time"),
        "midgpt_tpu/robustness/supervisor.py",
    )
    assert [(f.rule, f.line) for f in active] == [("GC012", 5)]
    # the SAME source outside injectable-clock territory never flags
    for path in ("midgpt_tpu/training/train.py", "tools/chaos_run.py"):
        active, _ = check_source(src, path)
        assert active == [], path


def test_gc012_plumbing_and_sleep_are_exempt():
    """`clock=time.perf_counter` is a reference (the plumbing itself, not
    a read) and `time.sleep` is a delay, not a measurement — the exact
    shapes sampling/serve.py and robustness/supervisor.py use."""
    src = """\
import time

class Engine:
    def __init__(self, clock=time.perf_counter, sleep_fn=time.sleep):
        self._clock = clock
        self._sleep = sleep_fn

    def step(self):
        time.sleep(0.01)
        return self._clock()
"""
    active, _ = check_source(src, "midgpt_tpu/sampling/serve.py")
    assert active == []


def test_gc012_suppressible_inline():
    src = """\
import time

def arrival_stamp():
    return time.time()  # graftcheck: disable=GC012 — wall-anchored arrival timestamp for logs
"""
    active, suppressed = check_source(src, "midgpt_tpu/sampling/server.py")
    assert active == []
    assert [(f.rule, f.line) for f in suppressed] == [("GC012", 4)]


# ----------------------------------------------------------------------
# Pass 3: clean counterparts and extra triggering shapes
# ----------------------------------------------------------------------


def test_gc009_clean_when_every_path_releases():
    """The disagg handoff shape: guarded raise cleans up in the handler,
    falsy acquisition carries no obligation, free(release(...)) retires
    the trie pages inline."""
    src = """\
def gather(prefill, allocator, tokens):
    pc = prefill.prefix_cache
    mr = pc.match(tokens)
    if mr is None:
        return None
    try:
        stage(mr)
    except Exception:
        allocator.free(pc.release(tokens, mr.pages, 0))
        raise
    allocator.free(pc.release(tokens, mr.pages, 0))
    return mr
"""
    active, _ = check_source(src, "clean_gc009.py")
    assert active == []


def test_gc009_double_release_and_discard():
    src = """\
def twice(allocator, n):
    pages = allocator.alloc(n)
    allocator.free(pages)
    allocator.free(pages)
"""
    active, _ = check_source(src, "double.py")
    assert [(f.rule, f.line) for f in active] == [("GC009", 4)]
    assert "released again" in active[0].message
    src = """\
def drop(prefill, tokens):
    prefill.prefix_cache.evict(tokens)
"""
    active, _ = check_source(src, "discard.py")
    assert [(f.rule, f.line) for f in active] == [("GC009", 2)]
    assert "discarded" in active[0].message


def test_gc009_transfer_into_container_is_a_release_funnel():
    """slot.pages.extend(got) moves ownership into engine state — the
    canonical adoption shape must not flag."""
    src = """\
def adopt(allocator, slot, n):
    got = allocator.alloc(n)
    if got is None:
        return False
    slot.pages.extend(got)
    return True
"""
    active, _ = check_source(src, "adopt.py")
    assert active == []


def test_gc009_follows_the_page_pool_by_name():
    """`pool.alloc(kind, n)` / `pool.free(kind, pages)` (sampling/pages.py
    PagePool) are acquisition and release sites like the allocator's own."""
    src = """\
def leak(self, n):
    got = self.pool.alloc(0, n)
    if got is None:
        return False
    return True

def fine(eng, trie, n):
    got = eng.pool.alloc(0, n)
    if got is None:
        eng.pool.free(0, trie.evict(n))
        return
    eng.pool.free(0, got)
"""
    active, _ = check_source(src, "pool.py")
    assert [(f.rule, f.line) for f in active] == [("GC009", 5)]


def test_gc009_refs_protocol():
    trie_src = """\
class _Node:
    def dec(self):
        self.refs -= 1
"""
    # outside the trie module: ANY .refs mutation is a protocol breach
    active, _ = check_source(trie_src, "server.py")
    assert [(f.rule, f.line) for f in active] == [("GC009", 3)]
    # inside it: a decrement still needs the adjacent underflow guard
    active, _ = check_source(trie_src, "prefix_cache.py")
    assert [(f.rule, f.line) for f in active] == [("GC009", 3)]
    assert "underflow" in active[0].message
    guarded = """\
class _Node:
    def dec(self):
        self.refs -= 1
        assert self.refs >= 0
"""
    active, _ = check_source(guarded, "prefix_cache.py")
    assert active == []


def test_gc010_direct_engine_call_flags_queued_command_clean():
    bad = """\
class Server:
    async def status(self):
        return self.engine.stats()
"""
    active, _ = check_source(bad, "srv.py")
    assert [(f.rule, f.line) for f in active] == [("GC010", 3)]
    # the blessed shape: mutation happens inside a queued command (nested
    # def) drained by the driver loop, not in the event-loop context
    ok = """\
import asyncio

class Server:
    async def submit(self, req):
        def do_submit():
            return self.engine.submit(req)
        return await asyncio.to_thread(do_submit)
"""
    active, _ = check_source(ok, "srv_ok.py")
    assert active == []


def test_gc010_single_mutation_with_await_is_clean():
    src = """\
import asyncio

class Server:
    async def run(self):
        self.running = True
        await asyncio.sleep(0)
        self.stopped = True
"""
    active, _ = check_source(src, "srv2.py")
    assert active == []


def test_gc011_bounded_domains_pass():
    """pow2 ladder, bucket normalizer, bool compare, literal menu — every
    blessed static-domain shape proves bounded."""
    src = """\
import functools

import jax

@functools.partial(jax.jit, static_argnums=(1, 2))
def step(x, n, flag):
    return x * n if flag else x

def _split_bucket(t):
    return 1 if t < 4096 else 4

def drive(x, budget, t):
    n = 1 << (budget.bit_length() - 1)
    x = step(x, n, budget > 0)
    return step(x, _split_bucket(t), False)
"""
    active, _ = check_source(src, "bounded.py")
    assert active == []


def test_gc011_init_frozen_self_attr_passes_late_store_flags():
    frozen = """\
import functools

import jax

@functools.partial(jax.jit, static_argnums=(1,))
def step(x, n):
    return x * n

class Engine:
    def __init__(self, chunk):
        self.chunk = chunk

    def decode(self, x):
        return step(x, self.chunk)
"""
    active, _ = check_source(frozen, "eng.py")
    assert active == []
    thawed = frozen.replace(
        "    def decode(self, x):",
        "    def retune(self, c):\n        self.chunk = c\n\n    def decode(self, x):",
    )
    active, _ = check_source(thawed, "eng2.py")
    assert [(f.rule) for f in active] == ["GC011"]


# ----------------------------------------------------------------------
# Pass 4: clean counterparts and extra triggering shapes
# ----------------------------------------------------------------------


def test_gc013_queued_command_worker_is_clean():
    """The blessed worker shape: results travel back through driver-owned
    queues/events; the worker never touches engine state directly."""
    src = """\
import threading

class Serve:
    def start(self):
        threading.Thread(target=self._worker, daemon=True).start()

    def _worker(self):
        self._cmds.append(("set_temperature", 0.0))
        self._landed.set()
"""
    active, _ = check_source(src, "srv.py")
    assert active == []


def test_gc013_blessed_to_thread_step_funnel_passes_others_flag():
    """`await asyncio.to_thread(self.engine.step)` is the ONE blessed
    off-loop engine touch (sampling/server.py driver); shipping any other
    callee to the thread pool makes it a worker context."""
    ok = """\
import asyncio

class Server:
    async def drive(self):
        await asyncio.to_thread(self.engine.step)
"""
    active, _ = check_source(ok, "ok.py")
    assert active == []
    bad = """\
import asyncio

class Server:
    async def drive(self):
        await asyncio.to_thread(self._drain)

    def _drain(self):
        self.pool.resize(4)
"""
    active, _ = check_source(bad, "bad.py")
    assert [(f.rule, f.line) for f in active] == [("GC013", 8)]


def test_gc013_on_expire_callback_is_a_worker_context():
    src = """\
class Train:
    def arm(self, wd):
        wd.sync(self._force, on_expire=self._expired)

    def _expired(self, step, waited):
        self.engine.abort()
"""
    active, _ = check_source(src, "wd.py")
    assert [(f.rule, f.line) for f in active] == [("GC013", 6)]


def test_gc014_one_shot_flag_handler_is_clean():
    """The robustness/preempt.py pattern: set pre-existing module flags,
    stamp via an injected clock parameter, restore the previous
    disposition one-shot — all blessed."""
    src = """\
import signal

_requested = False


def _on_term(signum, frame, _clock=None):
    global _requested
    _requested = True
    stamp = _clock() if _clock else None
    signal.signal(signum, signal.SIG_DFL)
    return stamp


def install():
    signal.signal(signal.SIGTERM, _on_term)
"""
    active, _ = check_source(src, "preempt_ok.py")
    assert active == []


def test_gc014_checkpoint_call_and_lock_in_handler_flag():
    src = """\
import signal

def _on_term(signum, frame):
    mngr.save(0, state)
    guard.acquire()

def install():
    signal.signal(signal.SIGTERM, _on_term)
"""
    active, _ = check_source(src, "preempt_bad.py")
    assert [(f.rule, f.line) for f in active] == [("GC014", 4), ("GC014", 5)]
    assert "checkpoint" in active[0].message
    assert "lock" in active[1].message


def test_gc015_quantized_page_tuple_is_clean():
    """The `_gather_pages` idiom (sampling/disagg.py): host-landed
    np.asarray pages under the blessed {k, v, k_scale, v_scale} keys and
    plain scalars everywhere else."""
    src = """\
import jax.numpy as jnp
import numpy as np

class Disagg:
    def gather(self, cache, idx, uid):
        blocks = {}
        blocks["k"] = np.asarray(jnp.take(cache.k, idx, axis=2))
        blocks["k_scale"] = np.asarray(jnp.take(cache.k_scale, idx, axis=2))
        item = HandoffItem(uid=uid, deadline=self._clock() + 1.0,
                           blocks=blocks, n_pages=2)
        self.handoff_queue.push(item)
"""
    active, _ = check_source(src, "disagg_ok.py")
    assert active == []


def test_gc015_device_array_and_bad_block_key_flag():
    src = """\
import jax.numpy as jnp

class Disagg:
    def gather(self, cache, idx, uid):
        blocks = {}
        blocks["k"] = jnp.take(cache.k, idx, axis=2)
        blocks["raw_logits"] = cache.logits
        self.handoff_queue.push(HandoffItem(uid=uid, blocks=blocks))
"""
    active, _ = check_source(src, "disagg_bad.py")
    assert [(f.rule, f.line) for f in active] == [
        ("GC015", 6),
        ("GC015", 7),
    ]
    assert "device array" in active[0].message
    assert "raw_logits" in active[1].message


def test_gc015_tracks_queue_constructor_assignment():
    """A queue bound from PageHandoffQueue(...) is a wire queue even when
    the attribute name carries no handoff/failover/spill hint."""
    src = """\
class Disagg:
    def __init__(self):
        self.queue = PageHandoffQueue(retries=3)

    def enqueue(self, uid):
        self.queue.push(HandoffItem(uid=uid, clock=self._clock))
"""
    active, _ = check_source(src, "q.py")
    assert [(f.rule, f.line) for f in active] == [("GC015", 6)]
    assert "clock callable" in active[0].message


def test_gc016_complete_raise_is_clean_undeclared_field_flags():
    ok = """\
def give_up(step, retries, d):
    raise CheckpointWriteError(
        f"save at {step} failed",
        step=step,
        attempts=retries,
        directory=d,
    )
"""
    active, _ = check_source(ok, "ok.py")
    assert active == []
    bad = """\
def shed(self, needed):
    raise BackpressureError(
        "no pages",
        needed_pages=needed,
        backlog_pages=0,
        budget_pages=1,
        retryable=True,
        retry_after_pages=needed,
    )
"""
    active, _ = check_source(bad, "bad.py")
    assert [(f.rule) for f in active] == ["GC016"]
    assert "retry_after_pages" in active[0].message


def test_gc016_registry_matches_live_class_signatures():
    """The declarative registry (analysis/error_contracts.py) must track
    the real constructors: every declared field is a keyword parameter of
    the class __init__, required fields have no default, optional fields
    do. A registry/class drift fails here, not at triage time."""
    import inspect

    from midgpt_tpu.analysis.error_contracts import ERROR_CONTRACTS
    from midgpt_tpu.robustness.errors import (
        CheckpointCorruptError,
        CheckpointWriteError,
        DivergenceError,
        StepHangError,
    )
    from midgpt_tpu.sampling.disagg import HandoffRetryExhausted
    from midgpt_tpu.sampling.fleet_proc import (
        ReplicaGoneError,
        TransportError,
        WireFrameError,
    )
    from midgpt_tpu.sampling.ops import HotSwapError, PoolResizeError
    from midgpt_tpu.sampling.serve import BackpressureError

    classes = {
        "DivergenceError": DivergenceError,
        "StepHangError": StepHangError,
        "CheckpointCorruptError": CheckpointCorruptError,
        "CheckpointWriteError": CheckpointWriteError,
        "HotSwapError": HotSwapError,
        "PoolResizeError": PoolResizeError,
        "BackpressureError": BackpressureError,
        "HandoffRetryExhausted": HandoffRetryExhausted,
        "TransportError": TransportError,
        "WireFrameError": WireFrameError,
        "ReplicaGoneError": ReplicaGoneError,
    }
    assert set(classes) == set(ERROR_CONTRACTS)
    for name, cls in classes.items():
        contract = ERROR_CONTRACTS[name]
        params = inspect.signature(cls.__init__).parameters
        for field in contract.required + contract.optional:
            assert field in params, f"{name}: `{field}` not a constructor param"
        declared = set(contract.required) | set(contract.optional)
        for pname, p in params.items():
            if pname in ("self", "message") or p.kind is not p.KEYWORD_ONLY:
                continue
            assert pname in declared, f"{name}: `{pname}` missing from registry"


# ----------------------------------------------------------------------
# jit-surface census + baseline diff
# ----------------------------------------------------------------------


_SURFACE_SRC = """\
import functools

import jax


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
def step(x, n):
    return x * n


def plain(x):
    return x + 1


fwd = jax.jit(plain)
params = jax.jit(lambda k: k * 2)(3)


def drive(x):
    return step(x, 1 if x.ndim > 1 else 2)
"""


def _census(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(_SURFACE_SRC)
    return jit_surface([str(tmp_path)], rel_to=str(tmp_path))


def test_jit_surface_census_records_all_three_forms(tmp_path):
    entries = {e["name"]: e for e in _census(tmp_path)}
    assert set(entries) == {"step", "fwd", "<inline:lambda#0>"}
    assert entries["step"]["form"] == "decorator"
    assert entries["step"]["static_argnums"] == [1]
    assert entries["step"]["donate_argnums"] == [0]
    # the only callsite passes a literal-menu IfExp: provably bounded
    assert entries["step"]["static_verdicts"] == {"n": "bounded"}
    assert entries["fwd"]["form"] == "rebinding"
    assert entries["<inline:lambda#0>"]["form"] == "inline"


def test_jit_surface_diff_flags_new_and_changed_allows_removed(tmp_path):
    entries = _census(tmp_path)
    assert diff_surface(entries, entries) == []
    # a brand-new wrapper fails until re-pinned
    missing_one = [e for e in entries if e["name"] != "fwd"]
    problems = diff_surface(entries, missing_one)
    assert any("new jit wrapper `fwd`" in p for p in problems)
    # a widened static set on a pinned wrapper fails
    import copy

    widened = copy.deepcopy(entries)
    for e in widened:
        if e["name"] == "step":
            e["static_argnums"] = [1, 2]
    problems = diff_surface(widened, entries)
    assert any("static_argnums" in p for p in problems)
    # removal is allowed (shrinking the compile surface needs no ceremony)
    assert diff_surface(missing_one, entries) == []


def test_jit_surface_verdict_degrades_on_unbounded_callsite(tmp_path):
    src = _SURFACE_SRC.replace(
        "    return step(x, 1 if x.ndim > 1 else 2)",
        "    return step(x, x.tolist().pop())",
    )
    p = tmp_path / "mod.py"
    p.write_text(src)
    entries = {e["name"]: e for e in jit_surface([str(p)])}
    assert entries["step"]["static_verdicts"] == {"n": "unproven"}


# ----------------------------------------------------------------------
# CLI contract
# ----------------------------------------------------------------------


def _run_cli(*args):
    env = dict(os.environ, PYTHONPATH=os.path.dirname(os.path.dirname(__file__)))
    return subprocess.run(
        [sys.executable, "-m", "midgpt_tpu.analysis", *args],
        capture_output=True,
        text=True,
        env=env,
        timeout=120,
    )


def test_cli_nonzero_with_rule_id_and_location_per_fixture(tmp_path):
    """The acceptance pin: the CLI exits nonzero on the fixture violations
    and names each one by rule ID and file:line."""
    expected = []
    for rule, (src, line) in FIXTURES.items():
        p = tmp_path / f"fixture_{rule.lower()}.py"
        p.write_text(src)
        expected.append((rule, str(p), line))
    proc = _run_cli("--json", str(tmp_path))
    assert proc.returncode == 1, proc.stderr
    rec, problems = check_bench_stdout(proc.stdout, "graftcheck")
    assert not problems, problems
    assert rec["count"] == len(FIXTURES)
    got = {(f["rule"], f["path"], f["line"]) for f in rec["findings"]}
    for rule, path, line in expected:
        assert (rule, path, line) in got, (rule, got)


def test_cli_exit_zero_on_clean_file(tmp_path):
    p = tmp_path / "clean.py"
    p.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return x + 1\n")
    proc = _run_cli(str(p))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_rules_subset(tmp_path):
    """--rules narrows the run; unknown rules are a usage error."""
    p = tmp_path / "two.py"
    p.write_text(FIXTURES["GC003"][0] + FIXTURES["GC006"][0])
    proc = _run_cli("--json", "--rules", "GC006", str(p))
    rec, problems = check_bench_stdout(proc.stdout, "graftcheck")
    assert not problems, problems
    assert [f["rule"] for f in rec["findings"]] == ["GC006"]
    assert _run_cli("--rules", "GC999", str(p)).returncode == 2


def test_cli_rules_subset_can_select_pass3_only(tmp_path):
    p = tmp_path / "life.py"
    p.write_text(FIXTURES["GC009"][0] + FIXTURES["GC006"][0])
    proc = _run_cli("--json", "--rules", "GC009", str(p))
    rec, problems = check_bench_stdout(proc.stdout, "graftcheck")
    assert not problems, problems
    assert [f["rule"] for f in rec["findings"]] == ["GC009"]
    assert rec["count"] == rec["pass3_count"] == 1


def test_cli_rules_subset_can_select_pass4_only(tmp_path):
    p = tmp_path / "conc.py"
    p.write_text(FIXTURES["GC016"][0] + FIXTURES["GC006"][0])
    proc = _run_cli("--json", "--rules", "GC016", str(p))
    rec, problems = check_bench_stdout(proc.stdout, "graftcheck")
    assert not problems, problems
    assert [f["rule"] for f in rec["findings"]] == ["GC016"]
    assert rec["count"] == rec["pass4_count"] == 1
    assert rec["pass3_count"] == 0


def test_cli_fail_on_new_reports_jit_surface_changes(tmp_path):
    """A jit wrapper absent from the committed manifest fails
    --fail-on-new even with zero findings: compile-surface growth is a
    reviewed artifact, not a drive-by."""
    p = tmp_path / "new_wrapper.py"
    p.write_text(
        "import jax\n\n@jax.jit\ndef brand_new_wrapper(x):\n    return x + 1\n"
    )
    proc = _run_cli("--json", "--fail-on-new", str(p))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rec, problems = check_bench_stdout(proc.stdout, "graftcheck")
    assert not problems, problems
    assert rec["count"] == rec["new_count"] == 0
    assert rec["jit_surface_count"] == 1 and rec["jit_surface_new"] == 1
    # without --fail-on-new the same file is informational only: exit 0
    assert _run_cli(str(p)).returncode == 0


def test_cli_fail_on_new_flags_findings_absent_from_baseline(tmp_path):
    """The committed baseline is empty (the tree is clean), so any fixture
    finding is NEW: --fail-on-new exits nonzero and reports new_count."""
    p = tmp_path / "leak.py"
    p.write_text(FIXTURES["GC009"][0])
    proc = _run_cli("--json", "--fail-on-new", str(p))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rec, problems = check_bench_stdout(proc.stdout, "graftcheck")
    assert not problems, problems
    assert rec["new_count"] == rec["count"] == 1


def test_cli_json_reports_pass3_stats(tmp_path):
    p = tmp_path / "clean.py"
    p.write_text("x = 1\n")
    proc = _run_cli("--json", str(p))
    rec, problems = check_bench_stdout(proc.stdout, "graftcheck")
    assert not problems, problems
    assert rec["pass3_count"] == 0 and rec["pass3_suppressed"] == 0
    assert rec["pass3_wall_ms"] >= 0


def test_graftcheck_cli_emits_conformant_json_line(tmp_path):
    """tools/graftcheck.py (the path-setup wrapper) --json: its line must
    satisfy the graftcheck profile, including the pass-3/pass-4 stats
    fields and the jit-surface census count."""
    p = tmp_path / "clean.py"
    p.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return x + 1\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "graftcheck.py"), "--json", str(p)],
        capture_output=True,
        text=True,
        cwd=str(tmp_path),  # the wrapper, not the cwd, must find the package
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    rec, problems = check_bench_stdout(proc.stdout, "graftcheck")
    assert not problems, problems
    assert rec["tool"] == "graftcheck"
    assert rec["count"] == 0 and rec["files_scanned"] == 1
    assert rec["pass3_count"] == 0 and rec["pass3_wall_ms"] >= 0
    assert rec["pass4_count"] == 0 and rec["pass4_wall_ms"] >= 0
    assert rec["jit_surface_count"] == 1  # the @jax.jit wrapper above


def test_checker_rejects_multiline_and_nonjson():
    rec, problems = parse_single_json_line('{"a": 1}\nextra line\n')
    assert any("exactly 1" in p for p in problems)
    rec, problems = parse_single_json_line("not json at all\n")
    assert rec is None and any("not valid JSON" in p for p in problems)


def test_checker_rejects_nan():
    """json.dumps happily emits bare NaN — which no strict consumer parses.
    The checker must treat it as a contract violation, not a number."""
    line = json.dumps({"metric": "m", "value": float("nan")}) + "\n"
    rec, problems = parse_single_json_line(line)
    assert rec is None and any("NaN" in p or "non-finite" in p for p in problems)


def test_graftcheck_checker_catches_pass4_field_drift():
    """The graftcheck profile holds on a synthetic record without running
    the CLI: dropping or mistyping any pass-4 / jit-surface stat field is
    a contract violation, not a number."""
    good = {
        "tool": "graftcheck", "count": 0, "suppressed": 0,
        "files_scanned": 1, "findings": [],
        "pass3_count": 0, "pass3_suppressed": 0, "pass3_wall_ms": 1.0,
        "pass4_count": 0, "pass4_suppressed": 0, "pass4_wall_ms": 1.0,
        "jit_surface_count": 3,
    }
    assert check_graftcheck(good) == []
    for field in (
        "pass4_count",
        "pass4_suppressed",
        "pass4_wall_ms",
        "jit_surface_count",
    ):
        missing = dict(good)
        missing.pop(field)
        assert any(field in p for p in check_graftcheck(missing)), field
    wrong_type = dict(good, pass4_count="0")
    assert any("pass4_count" in p for p in check_graftcheck(wrong_type))
    assert any(
        "jit_surface_count" in p
        for p in check_graftcheck(dict(good, jit_surface_count=2.5))
    )
