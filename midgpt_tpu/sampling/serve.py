"""Continuous-batching serving engine over the paged KV cache.

`engine.generate` serves ONE fixed batch: every request starts together,
pads to the longest prompt, and the whole batch runs until the last request
finishes — a tail of dead slots, and a (B, S)-sized cache however short the
requests are. This module serves a STREAM: requests are admitted into decode
slots the moment one frees (or a new one arrives), long prompts prefill in
bounded chunks interleaved with the running batch's decode steps, and K/V
live in a shared paged pool sized to the expected working set instead of
`n_slots * block_size` (models/gpt.py PagedKVCache). The pool's format, its
size, its books (allocators, the release funnel, the window rule, page
conservation) and its page tables are sampling/pages.py's (`PagePool`, one an
engine: `self.pool`); this module is the policy over it and the round.

Scheduling is host-side and runs every round (`ServeEngine.step`):

  1. **Admit** — waiting requests claim free slots (FCFS). Admission needs
     only enough free pages for the FIRST prefill chunk; later pages are
     allocated lazily as the request grows.
  2. **Prefill** — every waiting slot advances its prompt by at most
     `prefill_chunk` tokens (GPT.prefill_paged_chunk), so a 30k-token
     prompt costs each running generation at most one chunk of extra
     latency per round instead of stalling the batch for the whole prompt
     (the chunked-prefill lever, Sarathi/vLLM-style, adapted to XLA static
     shapes: the chunk is padded to a fixed width, so ONE compiled program
     a page bucket serves every chunk of every prompt). The round's chunks
     ride as the rows of one `(prefill_width, prefill_chunk)` batch, so the
     round reads the weights once for all of them (`PREFILL_ROWS`).
  3. **Decode** — all generating slots step together as one device program:
     a power-of-two-sized chain of `GPT.decode_step_paged` calls
     (`_serve_decode_chunk`, same dispatch-amortization scheme as
     engine.generate's DECODE_CHUNK, bounded compile set
     {decode_chunk, decode_chunk/2, ..., 1}). Page tables and lengths are
     plain jit inputs — admitting/finishing requests never recompiles.

With a draft model configured, step 3 becomes a SPECULATIVE round instead:
the draft proposes k tokens per slot against the paged cache (one scanned
program), the target scores all k+1 positions in one batched paged verify
forward, and a rejection sampler commits the longest valid prefix + one
corrected/bonus token — exactly the target's distribution, any acceptance
rate (sampling/spec.py; `_spec_round`; docs/SERVING.md "Speculative
decoding"). k adapts per slot from the recent acceptance EMA over the pow2
buckets [spec_k_min, spec_k_max]; rejected tail positions roll back
page-aligned (length counters reset, tail pages freed, device pool never
rewritten).

Round-overlap dispatch (docs/SERVING.md "Round-overlap dispatch") hides
per-dispatch host latency behind two composable levers, both off by
default and both compiled from the SAME `_serve_decode_group` program:
`overlap="group"` fuses `round_group` decode rounds into one dispatched
`lax.scan` (EOS / budget / page-boundary handling masks on device, so a
slot that finishes mid-group settles at the group edge exactly where a
sequence of classic rounds would), and `overlap="double"` additionally
dispatches round N+1 BEFORE round N's host post-processing runs
(`_step_overlapped`), chaining device-side token/length state between the
two in-flight programs. Scheduler decisions are one round late by
construction under "double" — an admission or eviction during round N's
host phase first appears in round N+2's dispatch — and greedy streams
stay bit-exact across every mode (tests/test_overlap.py).

When the pool runs dry, the scheduler EVICTS a younger running slot
(frees its pages, pushes the request back to the queue front with its
generated tokens folded into the prompt — recompute-style preemption), so
the oldest requests always make progress and the engine never deadlocks.
WHICH younger slot — like the admission order and the shed decision — is a
pluggable policy (`sampling/scheduler.py`): `FCFSScheduler` (the default:
queue-head admission, youngest-first eviction, budget-only shedding) or
`SLOScheduler` (earliest-deadline-first admission, most-slack-first
eviction, infeasible-deadline shedding). Policies are pure host code; the
compiled program set is policy-independent (tests/test_scheduler.py).

Robustness levers (each round starts with an expiry pass):

  * **Per-request deadline/TTL** — `submit(..., ttl_s=...)`: a request that
    is still queued or generating past its deadline is finished with
    `status="timeout"` (partial tokens returned) and its pages freed, so a
    stalled client cannot occupy pool pages forever. All deadline math runs
    on the injectable `clock=` callable (default `time.perf_counter`), so
    TTL behavior is testable with a fake clock instead of sleeps.
  * **Backpressure** — `max_backlog_pages` bounds the worst-case page
    demand of all live requests; `submit` raises BackpressureError beyond
    it instead of growing the queue (and the eviction churn) without bound.
    The exception carries `retry_after_pages` / `backlog_pages` /
    `retryable` so callers back off programmatically (sampling/server.py)
    instead of string-parsing the message.
  * **Cancellation** — `cancel(uid)` finishes a queued or running request
    immediately (status "cancelled", pages freed) without perturbing
    co-resident slots; the async front door maps client disconnects onto
    it (tests/test_serving.py pins page conservation and neighbor-token
    stability).
  * **Fault hooks** — `step()` consults the robustness/faults.py registry
    for the serving fault kinds (`kill_mid_decode`: the round's decode
    dispatch dies and every decode-ready slot is recompute-preempted;
    `poisoned_page`: one live page is corrupted in place, modeling HBM
    damage — page isolation keeps every other slot's stream intact).
    With an empty registry (always, in production) each hook is a scan
    over an empty list. Chaos scenarios: robustness/chaos_serve.py.

With `prefix_cache=True`, admissions walk a host-side radix trie over the
pool (sampling/prefix_cache.py): fully-matched prompt pages map into the
new slot's page table with a refcount taken and their prefill SKIPPED —
the slot starts at `length = matched` and chunk-prefills only the
unmatched tail (chunked prefill's traced `start` makes that free of new
programs). Departing slots release their pages through the trie, which
keeps complete committed pages for future matches — so a preemption victim
re-matches its own history on readmission instead of re-prefilling from
token 0. When the allocator runs dry, refcount-0 trie pages are reclaimed
(LRU) BEFORE any slot is preempted; a referenced trie page is never
reclaimed. Sharing is page-table indirection only: the compiled program
set is identical with the cache on or off (tests/test_recompile_pins.py),
greedy streams are bit-identical (tests/test_prefix_cache.py), and all
three cache modes work unchanged — int8 scales are indexed by physical
page so they are shared with their page, and speculative drafts attend
through the same shared tables (docs/SERVING.md "Prefix cache").

Streaming hooks: `on_token(uid, token, t)` fires per generated token and
`on_finish(FinishedRequest)` on every terminal transition (finish, EOS,
timeout, cancel) — the async server's per-token streaming rides these.

Greedy (temperature=0) serving is token-for-token identical to
`engine.generate` on the same prompt (parity pin in tests/test_sampling.py);
stochastic sampling draws from a different key stream (one key a prefill
call and a decode round, split over the slot batch inside the program) and
is only distributionally equivalent.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import inspect
import sys
import time
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np

from midgpt_tpu.kernels.attention_template import (
    block_census,
    block_pages,
    normalize_split_k,
)
from midgpt_tpu.kernels.decode_attention import resolve_paged_impl
from midgpt_tpu.models.gpt import CacheKind, GPTConfig, GPTParams, PagedKVCache
from midgpt_tpu.obs import DISABLED_SNAPSHOT, Observability
from midgpt_tpu.obs.trace import NULL_TRACER
from midgpt_tpu.robustness import faults
from midgpt_tpu.sampling.engine import sample_logits, warp_logits
from midgpt_tpu.sampling.pages import PageAllocator, PagePool, adopt_pages, join_pages, keep_state
from midgpt_tpu.sampling.prefix_cache import PrefixCache
from midgpt_tpu.sampling.scheduler import FCFSScheduler, Scheduler
from midgpt_tpu.sampling.spec import speculative_accept
from midgpt_tpu.utils.hlo import jit_cache_size, pool_relayouts, weight_copies
from midgpt_tpu.utils.stack_chunk import call_on_own_chunk

Array = jax.Array

# what a jit call runs in with obs off (`ServeEngine._call_mark`)
_NO_MARK = contextlib.nullcontext()


def _maybe_constrain(cache, mesh):
    """Pin a tp-sharded pool's out-sharding to its in-sharding inside the
    serving jits (no-op unsharded). Without the constraint GSPMD may pick a
    different output layout for the donated pool and the round-to-round
    donation degrades to a copy+reshard (parallel/serve_tp.constrain_cache)."""
    if mesh is None:
        return cache
    from midgpt_tpu.parallel.serve_tp import constrain_cache

    return constrain_cache(cache, mesh)


class _PoolProgram:
    """A serving jit that donates the KV pool (`cache`), plus a record of
    what it compiled on the kernel path, for the pool-layout census.

    Calls, `lower` and `_cache_size` are the wrapped jit's own. When a call
    compiles a program, its abstract arguments are kept (`texts` hands out
    its optimized text); for those with `attn_impl` resolving to 'kernel',
    `census` later counts the pool- or
    layer-sized copies in each such program's compiled text
    (PagedKVCache "Layout contract": 0 when the pool keeps one layout from
    the program's parameter to its result), and the instructions that write
    a matrix of a stacked parameter out again."""

    def __init__(self, jitted):
        self.jit = jitted
        self.__wrapped__ = jitted.__wrapped__
        self.__name__ = jitted.__name__
        self.lower = jitted.lower
        self._cache_size = jitted._cache_size
        self._sig = inspect.signature(jitted.__wrapped__)
        # label -> abstract (args, kwargs) of a kernel-path program, and
        # label -> relayout count once `census` has read its text
        self._compiled: tp.Dict[str, tp.Any] = {}
        self._called: tp.Set[str] = set()  # labels a call was made under
        self._kernel_path: tp.Set[str] = set()
        self._relayouts: tp.Dict[str, int] = {}
        self._weight_copies: tp.Dict[str, int] = {}  # beside it: utils/hlo.weight_copies
        self._texts: tp.Dict[str, str] = {}  # label -> optimized text, once `texts` has read it

    def __call__(self, *args, **kwargs):
        bound = self._sig.bind(*args, **kwargs)
        a = bound.arguments
        kernel = resolve_paged_impl(a.get("attn_impl", "gather")) == "kernel"
        # the pool is donated: describe it before the call
        pool = jax.tree.map(_abstract, a["cache"])
        statics = [f"{k}={a[k]}" for k in ("n_steps", "k_steps", "round_group") if k in a]
        widths = [
            f"{k}={jax.tree.leaves(a[k])[0].shape[-1]}"  # a family's tables: the first kind's
            for k in ("page_table", "page_table_row", "tokens", "drafts")
            if k in a
        ]
        label = " ".join(
            [self.__name__.lstrip("_"), *statics, *widths,
             f"pool={pool.pool_arrays()[0].dtype}"]
        )
        n_before = self._cache_size()
        if label in self._called:
            out = self.jit(*args, **kwargs)
        else:
            # A program not called yet: this call traces and lowers it. Those
            # ~80 frames go on a stack chunk of their own, or what the
            # lowering costs turns on the sizes of the frames under this call
            # (utils/stack_chunk.py). A program the label does not tell apart
            # from one called before (another engine's statics) compiles
            # from here.
            self._called.add(label)
            out = call_on_own_chunk(self.jit, *args, **kwargs)
        if self._cache_size() != n_before:  # this call compiled a program
            a["cache"] = pool
            self._compiled[label] = jax.tree.map(
                _abstract, (bound.args, bound.kwargs)
            )
            if kernel:
                self._kernel_path.add(label)
        return out

    def census(self) -> None:
        """Read the compiled text of every kernel-path program not read
        yet, once: `_relayouts[label]`, the pool- or layer-sized copies and
        transposes in it (utils/hlo.pool_relayouts), and
        `_weight_copies[label]`, the instructions that write a matrix of a
        STACKED parameter out again (utils/hlo.weight_copies over every
        leaf of the params, of which it reads those of three dims or more:
        the GPT's blocks stacked over layers, a family's experts; 0 when
        every matmul reaches such a weight where it lies).
        Lowering the recorded abstract arguments again finds the executable
        the call compiled, it does not compile."""
        for label, (args, kwargs) in self._compiled.items():
            if label in self._kernel_path and label not in self._relayouts:
                text = self.jit.lower(*args, **kwargs).compile().as_text()
                a = self._sig.bind(*args, **kwargs).arguments
                self._relayouts[label] = pool_relayouts(
                    text, [p.shape for p in a["cache"].pool_arrays()]
                )
                self._weight_copies[label] = weight_copies(
                    text, [w.shape for w in jax.tree.leaves(a["params"])]
                )

    def texts(self) -> tp.Dict[str, str]:
        """{program as compiled: its optimized HLO text}, for a
        reader that joins a traced op to the scope that opened it (the v5e
        trace names an op by its instruction and carries no scope path). As
        `census`: lowering the recorded arguments again finds the
        executable the call compiled. Each program's text is read once:
        several readers of one traced run ask (a family's scopes, then its
        kernels), and a lowering is seconds."""
        for label, (args, kwargs) in self._compiled.items():
            if label not in self._texts:
                self._texts[label] = self.jit.lower(*args, **kwargs).compile().as_text()
        return dict(self._texts)


def _abstract(a):
    """A jax.Array as the ShapeDtypeStruct that lowers like it (its
    sharding when committed), a numpy array or scalar (a round's arguments,
    which ride the call as they are) as the one of its shape and dtype;
    anything else as it is."""
    # a Tracer: the program called under another trace (tests)
    if isinstance(a, (np.ndarray, np.generic, jax.core.Tracer)):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    if not isinstance(a, jax.Array):
        return a
    return jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding if a.committed else None
    )


def _split_key(key, num: int = 2):
    """A sampled serving program's FIRST operation: `key` is the engine's
    key as the program before left it on the device, split here and never on
    the host (an eager split is a program dispatch of its own, with the
    device idle). Returns (the key the engine keeps for its next program,
    the `num - 1` keys this one samples under): the sequence of keys is a
    function of the seed and the order the programs were called in, the one
    a host-side split before each call gives. `key` None (a greedy program):
    as many Nones."""
    if key is None:
        return (None,) * num
    return tuple(jax.random.split(key, num))


@_PoolProgram
@functools.partial(
    jax.jit, static_argnums=(0, 7, 8, 9, 10, 11), donate_argnums=(5,)
)
def _serve_prefill_chunk(
    config, params, tokens, start, n_valid, cache, page_table_row, mesh=None,
    attn_impl: str = "gather",
    temperature: float = 0.0,
    top_k=None,
    top_p=None,
    key=None,
):
    """One prompt chunk a row into the pool, and each row's next token
    sampled at its end: `tokens` (W, prefill_chunk), `start` / `n_valid`
    (W,), `page_table_row` (W, pages), an empty row having n_valid 0. An
    engine of width 1 makes the family's one-row call (models/__init__.py):
    `tokens` (1, prefill_chunk), SCALAR `start` / `n_valid`, the slot's own
    table row(s). `attn_impl` (the engine's resolved choice) selects the K/V
    write of every family and the lowering of the GPT's attention (the
    paged-attention template or the XLA gather: GPT.prefill_paged_chunk);
    every other family's prefill attends in XLA on every backend.

    What the family hands out is brought to one row of logits a slot, those
    of its last valid position: (W, V) as it is, the one-row call's (1, T, V)
    at `n_valid - 1`, its (1, 1, V) as it is. The token of each row is
    sampled from that row as `_serve_decode_chunk`'s step samples: the f32
    argmax at `temperature` 0 (static, with `top_k` / `top_p`), else
    `sample_logits` under a key split off `key`, the engine's (`_split_key`),
    one key over the rows. Returns (tokens (W,) int32, rows (W, V), cache,
    the engine's next key): the first token of a row whose prompt this chunk
    ends, and THE logits it was sampled from; an empty row's pair means
    nothing."""
    next_key, key = _split_key(key)
    logits, cache = config.model().prefill_paged_chunk(
        config, params, tokens, start, n_valid, cache, page_table_row,
        attn_impl=attn_impl, mesh=mesh,
    )
    if logits.ndim == 3:  # the one-row call: every position's, or the last's
        if logits.shape[1] > 1:
            logits = jax.lax.dynamic_index_in_dim(
                logits, n_valid - 1, axis=1, keepdims=False
            )
        else:
            logits = logits[:, 0]
    if temperature == 0.0:
        first = jnp.argmax(logits.astype(jnp.float32), axis=-1)
    else:
        first = sample_logits(logits, key, temperature, top_k, top_p)
    return first.astype(jnp.int32), logits, _maybe_constrain(cache, mesh), next_key


@_PoolProgram
@functools.partial(
    jax.jit, static_argnums=(0, 7, 8, 9, 10, 11, 13, 14), donate_argnums=(3,)
)
def _serve_decode_chunk(
    config,
    params,
    token,  # (B,) int32
    cache,  # PagedKVCache (donated)
    page_table,  # (B, max_pages) int32
    lengths,  # (B,) int32
    active,  # (B,) bool
    n_steps: int,
    temperature: float,
    top_k,
    top_p,
    attn_impl: str,
    key=None,
    mesh=None,  # static (Mesh hashes) — tp serving mesh, None = single chip
    split_k: int = 1,  # static — key partitions per slot (docs/SERVING.md)
):
    """n_steps decode+sample steps for the whole slot batch as ONE device
    program. Inactive slots hold their token and length (their writes land
    on the sink page). `key` is the engine's: the round's own is split off it
    first (`_split_key`). Returns (cache, tokens (n_steps, B), the engine's
    next key)."""
    next_key, key = _split_key(key)

    def body(carry, _):
        token, cache, lengths, key = carry
        if key is not None:
            key, k = jax.random.split(key)
        else:
            k = None
        logits, cache = config.model().decode_step_paged(
            config, params, token, cache, page_table, lengths, active,
            attn_impl=attn_impl, mesh=mesh, split_k=split_k,
        )
        cache = _maybe_constrain(cache, mesh)
        if temperature == 0.0:
            nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1)
        else:
            nxt = sample_logits(logits, k, temperature, top_k, top_p)
        nxt = jnp.where(active, nxt.astype(token.dtype), token)
        lengths = lengths + active.astype(lengths.dtype)
        return (nxt, cache, lengths, key), nxt

    (_, cache, _, _), toks = jax.lax.scan(
        body, (token, cache, lengths, key), None, length=n_steps
    )
    return cache, toks, next_key


@_PoolProgram
@functools.partial(jax.jit, static_argnums=(0, 7, 8, 9), donate_argnums=(3,))
def _serve_decode_logits(
    config, params, token, cache, page_table, lengths, active,
    attn_impl: str, mesh=None, split_k: int = 1,
):
    """`_serve_decode_chunk`'s step ONCE, handing out its logits and sampling
    nothing (`ServeEngine.next_logits`: checks, not the serving loop). The
    K/V it writes are the ones the round that follows writes again; a STATE
    row is handed back as it came in (`pages.keep_state`: the round would
    apply the step a second time). Returns (logits (B, V), cache)."""
    logits, stepped = config.model().decode_step_paged(
        config, params, token, cache, page_table, lengths, active,
        attn_impl=attn_impl, mesh=mesh, split_k=split_k,
    )
    return logits, _maybe_constrain(keep_state(stepped, cache), mesh)


# Rows a prefill program should bring to each weight it reads. A bf16 matmul
# of R rows against a (D, N) weight does 2 R D N FLOP for 2 D N bytes of
# weight: R FLOP a byte. The v5e's ridge is 197e12 FLOP/s / 819e9 B/s = 240
# FLOP a byte, so under ~240 rows the program waits for the weights, and a
# 16-token chunk alone reads the whole model for a fifteenth of that. 256 is
# the ridge rounded to a power of two. The chunks of a round's prefilling
# slots ride as rows of one batch until they make up that many.
#
# That is a DENSE weight's arithmetic: it multiplies every token row. A
# routed expert multiplies `top_k / n_experts` of them (Trinity: a sixteenth:
# a 512-token chunk brings each of 128 experts 32 pairs, and streams all
# 128), so a family with routed layers asks for more token rows a call: its
# `prefill_rows(config, PREFILL_ROWS)` (ops/moe.py `moe_prefill_rows`: the
# rows that bring an expert HALF this many pairs on average, since the most
# loaded expert's run is over twice the mean and a run past the kernel's
# 256-row block streams its expert again; Trinity: 2,048 rows, four chunks).
PREFILL_ROWS = 256


def prefill_width(max_slots: int, prefill_chunk: int, rows: tp.Optional[int] = None) -> int:
    """Chunks (slots) a prefill program takes: as many as bring `rows`
    token rows to a call, no more than there are slots. `rows` is what the
    FAMILY states for its configuration (`prefill_rows(config,
    PREFILL_ROWS)`, models/__init__.py): `PREFILL_ROWS` itself where every
    weight sees every row (the default), more where a weight sees a share
    of them (a routed expert). STATIC for an engine: one program a page
    bucket whatever the number of prefilling slots (a round with more goes
    in groups; a call with fewer leaves empty rows), so the compile set
    does not grow and a warm-up that runs requests alone visits every
    program. A chunk that is past `rows` on its own (a dense family's 512
    tokens) gives 1: the one-row call, a slot at a time."""
    rows = PREFILL_ROWS if rows is None else rows
    return min(max_slots, max(1, rows // prefill_chunk))


# Cap on the fused multi-round group size (docs/SERVING.md "Round-overlap
# dispatch"): k rounds per dispatched program trade scheduling granularity
# (admissions/evictions only land at group edges) for dispatch amortization,
# and past ~8 the granularity cost dominates on any realistic trace.
_ROUND_GROUP_CAP = 8


def _round_group_bucket(group: int) -> int:
    """Clamp a requested multi-round group size to [1, _ROUND_GROUP_CAP]
    and floor it to a power of two — the same pow2 ladder every other
    static jit knob (decode chunk, page bucket, split_k) rides, so the
    compile set stays logarithmic and the GC011 static-domain prover can
    see the bound lexically."""
    group = max(1, min(int(group), _ROUND_GROUP_CAP))
    return 1 << (group.bit_length() - 1)


@_PoolProgram
@functools.partial(
    jax.jit,
    static_argnums=(0, 12, 13, 14, 15, 16, 17, 19, 20),
    donate_argnums=(3,),
)
def _serve_decode_group(
    config,
    params,
    token,  # (B,) int32 — host view of each slot's pending token
    cache,  # PagedKVCache (donated)
    page_table,  # (B, max_pages) int32
    lengths,  # (B,) int32 — host view of committed lengths
    active,  # (B,) bool — batch membership at dispatch
    eos,  # (B,) int32 — per-slot EOS id, -1 when the request has none
    max_len,  # (B,) int32 — absolute settle bound per slot (see below)
    chain_mask,  # (B,) bool — slots continuing from an unsettled group
    chain_token,  # (B,) int32 — device-side pending token for chained slots
    chain_len,  # (B,) int32 — device-side lengths for chained slots
    n_steps: int,
    round_group: int,
    temperature: float,
    top_k,
    top_p,
    attn_impl: str,
    key=None,
    mesh=None,  # static (Mesh hashes) — tp serving mesh, None = single chip
    split_k: int = 1,  # static — key partitions per slot (docs/SERVING.md)
):
    """`n_steps * round_group` decode+sample steps as ONE dispatched
    program — the fused multi-round group of the round-overlap scheme
    (docs/SERVING.md "Round-overlap dispatch"). Differences from
    `_serve_decode_chunk`, all serving the settle-at-the-boundary rule:

      * **Device-side finish masking.** A slot stops stepping the moment
        its length reaches `max_len` (its generation budget or provisioned
        pages, whichever binds first) or it emits its EOS token —
        `step_active` masks the K/V write, the emit, and the length
        advance, so a finished slot can NEVER write past the pages it was
        provisioned at dispatch (an out-of-range page-table gather clamps
        to a REAL page, so an unmasked overrun would corrupt a neighbor's
        — or the trie's — committed K/V). The emitted mask is returned so
        the host commits exactly the tokens a sequence of classic rounds
        would have.
      * **Chained carry-in.** Under double-buffering the previous group is
        still in flight at dispatch: the host's token/length view of its
        slots is one round stale, so the true values ride in on
        `chain_token`/`chain_len` (the previous program's outputs, never
        forced) and are merged under `chain_mask` INSIDE this program —
        one dispatch per round, no eager merge ops between dispatches.

    `round_group` is a pow2-bucketed static (`_round_group_bucket`), so
    the compile set stays one program per (n_steps bucket, page bucket,
    round_group) — pinned by tests/test_recompile_pins.py. Returns
    (cache, toks (T, B), emitted (T, B) bool, tok_fin (B,), len_fin (B,),
    the engine's next key) with T = n_steps * round_group; tok_fin/len_fin
    seed the next group's chain without settling this one. `key` is the
    engine's, the group's own split off it first (`_split_key`)."""
    next_key, key = _split_key(key)
    token = jnp.where(chain_mask, chain_token, token)
    lengths = jnp.where(chain_mask, chain_len, lengths)

    def body(carry, _):
        token, cache, lengths, active, key = carry
        if key is not None:
            key, k = jax.random.split(key)
        else:
            k = None
        # Pre-step mask: the write for this step lands at position
        # `lengths`, so it must be gated BEFORE the decode step runs.
        step_active = active & (lengths < max_len)
        logits, cache = config.model().decode_step_paged(
            config, params, token, cache, page_table, lengths, step_active,
            attn_impl=attn_impl, mesh=mesh, split_k=split_k,
        )
        cache = _maybe_constrain(cache, mesh)
        if temperature == 0.0:
            nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1)
        else:
            nxt = sample_logits(logits, k, temperature, top_k, top_p)
        nxt = jnp.where(step_active, nxt.astype(token.dtype), token)
        lengths = lengths + step_active.astype(lengths.dtype)
        hit_eos = step_active & (eos >= 0) & (nxt == eos)
        active = active & ~hit_eos
        return (nxt, cache, lengths, active, key), (nxt, step_active)

    (tok_fin, cache, len_fin, _, _), (toks, emitted) = jax.lax.scan(
        body,
        (token, cache, lengths, active, key),
        None,
        length=n_steps * round_group,
    )
    return cache, toks, emitted, tok_fin, len_fin, next_key


@_PoolProgram
@functools.partial(
    jax.jit, static_argnums=(0, 7, 8, 9, 10, 11, 13, 14), donate_argnums=(3,)
)
def _spec_draft_chunk(
    config,  # the DRAFT model's GPTConfig
    params,  # the DRAFT model's params
    token,  # (B,) int32 — each slot's pending token
    cache,  # draft PagedKVCache (donated)
    page_table,  # (B, max_pages) int32 — SHARED with the target pool
    lengths,  # (B,) int32
    active,  # (B,) bool
    k_steps: int,
    temperature: float,
    top_k,
    top_p,
    attn_impl: str,
    key=None,
    mesh=None,  # static — tp serving mesh, None = single chip
    split_k: int = 1,  # static — key partitions per slot
):
    """k_steps autoregressive draft proposals for the whole slot batch as
    ONE device program: a scan of paged decode steps of the draft model
    against the draft pool. Returns (cache, drafts (k, B) int32, probs
    (k, B, V) f32) where probs[i] is the warped draft distribution proposal
    i was drawn from — the q_i the verify program's rejection sampler
    needs. Compiled once per (k bucket, page bucket), independent of
    request mix (pinned by tests/test_recompile_pins.py). `key` is the
    engine's: the round's three-way split is made here first (`_split_key`),
    and after the three come the engine's next key and the key
    `_spec_verify_chunk` takes, neither ever on the host."""
    next_key, key, verify_key = _split_key(key, 3)

    def body(carry, _):
        token, cache, lengths, key = carry
        if key is not None:
            key, k = jax.random.split(key)
        logits, cache = config.model().decode_step_paged(
            config, params, token, cache, page_table, lengths, active,
            attn_impl=attn_impl, mesh=mesh, split_k=split_k,
        )
        cache = _maybe_constrain(cache, mesh)
        lf = logits.astype(jnp.float32)
        if temperature == 0.0:
            probs = jax.nn.softmax(lf, axis=-1)
            nxt = jnp.argmax(lf, axis=-1)
        else:
            warped = warp_logits(lf, temperature, top_k, top_p)
            probs = jax.nn.softmax(warped, axis=-1)
            nxt = jax.random.categorical(k, warped, axis=-1)
        nxt = jnp.where(active, nxt.astype(token.dtype), token)
        lengths = lengths + active.astype(lengths.dtype)
        return (nxt, cache, lengths, key), (nxt, probs)

    (_, cache, _, _), (toks, probs) = jax.lax.scan(
        body, (token, cache, lengths, key), None, length=k_steps
    )
    return cache, toks, probs, next_key, verify_key


@_PoolProgram
@functools.partial(
    jax.jit, static_argnums=(0, 9, 10, 11, 12, 14, 15), donate_argnums=(5,)
)
def _spec_verify_chunk(
    config,
    params,
    token,  # (B,) int32 — each slot's pending token
    drafts,  # (k, B) int32 — _spec_draft_chunk output, never landed on host
    draft_probs,  # (k, B, V) f32
    cache,  # target PagedKVCache (donated)
    page_table,
    lengths,
    active,
    temperature: float,
    top_k,
    top_p,
    attn_impl: str,
    key=None,
    mesh=None,  # static — tp serving mesh, None = single chip
    split_k: int = 1,  # static — key partitions per slot
):
    """One batched paged verify forward over [pending, d_1..d_k] plus the
    rejection sampler (sampling/spec.py): returns (cache, n_accept (B,),
    out (B, k+1)) — the host emits out[b, :n_accept[b] + 1] per active
    slot. k rides the drafts shape, so the program set is one per (k
    bucket, page bucket) like the draft program."""
    tokens = jnp.concatenate(
        [token[:, None], drafts.T.astype(token.dtype)], axis=1
    )  # (B, k+1)
    logits, cache = config.model().verify_step_paged(
        config, params, tokens, cache, page_table, lengths, active,
        attn_impl=attn_impl, mesh=mesh, split_k=split_k,
    )
    cache = _maybe_constrain(cache, mesh)
    n_accept, out = speculative_accept(
        logits,
        jnp.transpose(draft_probs, (1, 0, 2)),
        drafts.T.astype(jnp.int32),
        key,
        temperature,
        top_k,
        top_p,
    )
    return cache, jnp.where(active, n_accept, 0), out


# Accepted `cache_dtype` spellings. "bf16" is the TPU serving default;
# "int8" selects the quantized pool (PagedKVCache int8 storage mode —
# halves decode-attention HBM traffic and doubles pages-per-byte at the
# same pool budget, docs/SERVING.md "Quantized KV cache"); float32 exists
# for the CPU test mesh, where exact greedy parity with engine.generate's
# f32 math is what the serving pins assert.
_CACHE_DTYPES = {
    "bf16": jnp.bfloat16,
    "bfloat16": jnp.bfloat16,
    "int8": jnp.int8,
    "f32": jnp.float32,
    "float32": jnp.float32,
}


def normalize_cache_dtype(dtype) -> jnp.dtype:
    """'bf16' | 'int8' | 'float32' | a jnp dtype -> the jnp dtype."""
    if isinstance(dtype, str):
        if dtype not in _CACHE_DTYPES:
            raise ValueError(
                f"unknown cache dtype {dtype!r} (one of {sorted(_CACHE_DTYPES)})"
            )
        return jnp.dtype(_CACHE_DTYPES[dtype])
    return jnp.dtype(dtype)


class BackpressureError(RuntimeError):
    """Admission was refused — the caller should shed load or (when
    `retryable`) retry later, instead of the request sitting in an
    unbounded queue (or thrashing the pool with evictions) indefinitely.

    Structured fields (so callers never string-parse the message):

      needed_pages     worst-case pages the refused request would commit
      backlog_pages    worst-case pages already committed to live requests
      budget_pages     the engine's `max_backlog_pages` (None = unbounded)
      retryable        False when waiting cannot help (e.g. the
                       SLOScheduler shed an already-infeasible deadline);
                       True for capacity sheds — pages free as requests
                       finish, so a bounded retry-with-backoff is sane
                       (sampling/server.py does exactly that)
      retry_after_pages  pages that must free before a retry can admit
                       (None when any ingredient is unknown)
    """

    def __init__(
        self,
        message: str,
        *,
        needed_pages: tp.Optional[int] = None,
        backlog_pages: tp.Optional[int] = None,
        budget_pages: tp.Optional[int] = None,
        retryable: bool = True,
    ):
        super().__init__(message)
        self.needed_pages = needed_pages
        self.backlog_pages = backlog_pages
        self.budget_pages = budget_pages
        self.retryable = retryable

    @property
    def retry_after_pages(self) -> tp.Optional[int]:
        if None in (self.needed_pages, self.backlog_pages, self.budget_pages):
            return None
        return max(0, self.backlog_pages + self.needed_pages - self.budget_pages)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (T0,) int32
    max_new_tokens: int
    eos_id: tp.Optional[int] = None
    deadline: tp.Optional[float] = None  # absolute time.perf_counter() expiry
    t_submit: float = 0.0  # engine clock at submit(); kept across preemptions


@dataclasses.dataclass
class _Slot:
    request: Request
    admit_order: int
    # pages[k]: the slot's pages of cache kind k (models/__init__.py
    # `cache_kinds`; the GPT has one kind), a LOGICAL list: entry j holds
    # positions [j*ps, (j+1)*ps), -1 once window-reclaimed. What knows one
    # kind only (prefix cache, spill tier, speculation's rollback, resize;
    # each refused where there are several) reads pages[0].
    pages: tp.List[tp.List[int]]
    # reclaimed_to[k]: the first logical page of kind k the window rule has
    # not passed yet (every page below it is freed, the sink prefix apart), so
    # the rule scans forward only
    reclaimed_to: tp.List[int]
    length: int = 0  # tokens in the paged cache
    prompt_pos: int = 0  # prompt tokens prefilled so far
    # pages[0][:n_shared] are prefix-cache trie entries this slot holds one
    # reference each on (prefix_cache engines only; 0 otherwise). The slot
    # never writes them: match caps at len(prompt) - 1 tokens and
    # insert_live shares only complete prompt pages, while every write
    # after admission lands at a position >= length >= the shared span.
    n_shared: int = 0
    # the slot's row of the family's STATE kind (sampling/pages.py
    # `claim_state` writes it, `release` takes it back); -1: none
    state_row: int = -1
    generated: tp.List[int] = dataclasses.field(default_factory=list)
    token_times: tp.List[float] = dataclasses.field(default_factory=list)
    # speculative-decoding state (draft engines only): current per-slot
    # draft length and the acceptance EMA that adapts it. The EMA starts
    # optimistic (1.0) so the first round can never halve k before any
    # evidence exists.
    spec_k: int = 1
    accept_ema: float = 1.0
    # obs only (the `req.prefill` end args): prompt tokens the prefix cache
    # skipped at admission, and the round the slot was admitted in
    skipped: int = 0
    admit_round: int = 0
    @property
    def prefilling(self) -> bool:
        return self.prompt_pos < len(self.request.prompt)

    @property
    def remaining(self) -> int:
        return self.request.max_new_tokens - len(self.generated)


@dataclasses.dataclass
class FinishedRequest:
    uid: int
    tokens: np.ndarray  # prompt + generated
    token_times: tp.List[float]  # wall-clock completion time per new token
    status: str = "ok"  # "ok" | "timeout" (deadline expired before finish)


@dataclasses.dataclass
class _InflightRound:
    """A dispatched-but-unsettled decode group (round-overlap dispatch).

    Holds the group program's UNFORCED device outputs plus the host-side
    identity snapshot needed to settle it later: `slots` pins the exact
    _Slot objects that were in the batch, so a settle after an eviction /
    cancel / timeout skips any index whose slot object changed — the
    in-flight tokens for a departed slot are simply discarded (recompute
    preemption regenerates them bit-exactly; greedy streams are batch-
    composition-independent). `worst_len` is the worst-case post-settle
    length per slot — what the NEXT dispatch must assume for a chained
    slot whose true device-side length (`len_fin`) it merges in-program.
    """

    toks: Array  # (T, B) int32, unforced
    emitted: Array  # (T, B) bool, unforced
    tok_fin: Array  # (B,) int32, unforced — next group's chain_token
    len_fin: Array  # (B,) int32, unforced — next group's chain_len
    n_steps: int  # T = n * round_group
    active_idx: tp.List[int]
    slots: tp.List[_Slot]
    worst_len: np.ndarray  # (max_slots,) int32
    round_no: int
    t0: float
    t1: float
    # for obs: the readings inside t0 -> t1, and which term set the steps
    cuts: tp.Tuple[float, tp.Optional[float], float]
    limit: str
    # the group's dispatch number and page bucket; obs on only: the thread's
    # CPU clock where t0 and t1 were read, the kernel grid's (swept, live)
    call: int = 0
    bucket: int = 0
    cpu: tp.Tuple[float, float] = (0.0, 0.0)
    blocks: tp.Optional[tp.Tuple[int, int]] = None


class ServeEngine:
    """Host-side continuous-batching scheduler (module docstring)."""

    def __init__(
        self,
        config: GPTConfig,
        params: GPTParams,
        *,
        max_slots: int = 4,
        num_pages: tp.Optional[int] = None,
        pool_hbm_bytes: tp.Optional[int] = None,
        page_size: int = 8,
        prefill_chunk: int = 16,
        decode_chunk: int = 8,
        temperature: float = 0.0,
        top_k: tp.Optional[int] = None,
        top_p: tp.Optional[float] = None,
        seed: int = 0,
        cache_dtype=jnp.bfloat16,
        attn_impl: str = "auto",
        split_k="auto",  # "auto" | int — key partitions per attention call
        overlap: str = "off",  # "off" | "double" | "group" (SERVING.md)
        round_group: int = 1,  # fused rounds per dispatch (pow2-bucketed)
        max_backlog_pages: tp.Optional[int] = None,
        prefix_cache: bool = False,
        draft_params: tp.Optional[GPTParams] = None,
        draft_config: tp.Optional[GPTConfig] = None,
        draft_shares_cache: bool = False,
        spec_k_max: int = 4,
        spec_k_min: int = 1,
        spec_adapt: bool = True,
        scheduler: tp.Optional[Scheduler] = None,
        clock: tp.Callable[[], float] = time.perf_counter,
        on_token: tp.Optional[tp.Callable[[int, int, float], None]] = None,
        on_finish: tp.Optional[tp.Callable[["FinishedRequest"], None]] = None,
        # (uid, logits (V,)): the prefill program's logits at a prompt's last
        # position, the row its first token was sampled from (checks). Set,
        # it is what brings a call's logits to the host at all.
        on_first_logits: tp.Optional[tp.Callable[[int, np.ndarray], None]] = None,
        mesh=None,  # Optional[jax.sharding.Mesh] — parallel/serve_tp.py
        obs: tp.Optional[Observability] = None,
        obs_tid: str = "engine",
        weights_version: str = "inline",
        watchdog=None,  # Optional[robustness.watchdog.StepWatchdog]
    ):
        assert decode_chunk & (decode_chunk - 1) == 0, "decode_chunk: power of two"
        config.check_serving("ServeEngine")  # a family this engine holds no cache for stops here
        # The model is reached through what every served family's namespace
        # provides (models/__init__.py), never by name. `kinds`: the kinds of
        # paged cache its layers need, each with a pool, an allocator and a
        # page table of its own here; the first is the one every mechanism
        # that knows one kind reads (`allocator`, `slot.pages[0]`, `num_pages`).
        self.config = config
        self.model = config.model()
        # A kind that is no `CacheKind` is a STATE kind, a row a slot
        # (sampling/pages.py "State kinds"): the pool owner's, and here only
        # what is refused for it.
        kinds = self.model.cache_kinds(config)
        self.kinds = tuple(k for k in kinds if isinstance(k, CacheKind))
        self.state_kinds = tuple(k for k in kinds if not isinstance(k, CacheKind))
        if len(self.kinds) > 1 or self.state_kinds:
            # what is not wired over several kinds of pages, or beside rows
            # of state that no page mechanism can move, by mechanism
            for on, what in (
                (prefix_cache, "the prefix cache (pages of several kinds under one trie)"),
                (draft_params is not None or draft_config is not None,
                 "speculative decoding (no verify step over a several-kind cache; the drafter is left out)"),
                (normalize_cache_dtype(cache_dtype) == jnp.int8, "int8 pools (no quantised write or read per kind)"),
                (mesh is not None, "a serving mesh (tp / ep > 1: no exchange of routed tokens is run)"),
                (pool_hbm_bytes is not None, "byte-budgeted pool sizing (pool_hbm_bytes: one budget over several pools)"),
            ):
                if on:
                    self._refuse_several_kinds(what)
        # ---- tp serving mesh (docs/SERVING.md "Mesh-sharded serving") ----
        # Params shard by the megatron training rules (vocab-parallel off so
        # logits stay replicated for the host-side first-token argmax), the
        # paged pools shard heads over 'tp', and EVERY scheduler-facing jit
        # input — page tables, lengths, tokens — stays a replicated host
        # array: the trie/allocator/scheduler below never learn the mesh
        # exists. The mesh rides the serving jits as a trailing static arg,
        # so a sharded and an unsharded engine in one process keep disjoint
        # compile-cache entries and mesh=None stays bit-for-bit the
        # single-chip behavior.
        self.mesh = mesh
        if mesh is not None:
            from midgpt_tpu.parallel import serve_tp as _stp

            n_tp = int(mesh.shape["tp"])
            for nm, c in (("target", config), ("draft", draft_config)):
                if c is not None and c.n_head % n_tp:
                    raise ValueError(
                        f"{nm} n_head={c.n_head} not divisible by mesh "
                        f"tp={n_tp} — the pool shards whole heads"
                    )
                if c is not None and c.kv_heads % n_tp:
                    # GQA pool shards whole KV heads; with H_q % tp == 0 the
                    # shard boundary then falls between whole query groups.
                    raise ValueError(
                        f"{nm} n_kv_heads={c.kv_heads} not divisible by "
                        f"mesh tp={n_tp} — the pool shards whole KV heads"
                    )
            params = _stp.put_sharded(
                params, _stp.serve_param_specs(params, mesh), mesh
            )
            if draft_params is not None:
                draft_params = _stp.put_sharded(
                    draft_params, _stp.serve_param_specs(draft_params, mesh), mesh
                )
        self.config = config
        self.params = params
        self.scheduler = scheduler if scheduler is not None else FCFSScheduler()
        self._clock = clock
        # Observability (midgpt_tpu/obs/): spans + round decomposition +
        # metrics, all host-side. obs=None keeps NULL_TRACER in every
        # instrumentation site — zero clock reads, zero ring appends —
        # and the scheduling/token path is bit-identical either way
        # (tests/test_obs.py pins parity; tests/test_recompile_pins.py
        # pins that the toggle compiles nothing: spans never cross the
        # jit boundary, so no static, no program).
        self.obs = obs
        self._trace = obs.tracer if obs is not None else NULL_TRACER
        self._obs_tid = obs_tid
        # uid -> (open request phase, its start): obs-on only (_req_phase)
        self._req_open: tp.Dict[int, tp.Tuple[str, float]] = {}
        # obs only: since a decode round began its commit, [tokens appended
        # (`_append_token`), requests that ended (`_finish`), seconds inside
        # the client's on_token]; `record_round` takes them
        self._commit: tp.List[tp.Any] = [0, 0, 0.0]
        # Hung-dispatch watchdog (robustness/watchdog.py), same injection
        # discipline as clock/obs: None (default) leaves the decode round's
        # force a plain np.asarray — no thread, no event, nothing for the
        # recompile pins to see. Set, it bounds the round's device sync so a
        # wedged device ends in StepHangError instead of a hung server.
        self.watchdog = watchdog
        self.on_token = on_token
        self.on_finish = on_finish
        self.on_first_logits = on_first_logits
        self.page_size = page_size
        self.max_slots = max_slots
        self.prefill_chunk = prefill_chunk
        # rows of the prefill program: the module's rule at this engine's
        # shapes and the token rows the family asks for at its configuration;
        # 1 for a family whose `prefill_paged_chunk` takes one row
        self.prefill_width = (
            prefill_width(max_slots, prefill_chunk, self.model.prefill_rows(config, PREFILL_ROWS))
            if self.model.prefill_batched else 1
        )
        self.decode_chunk = decode_chunk
        self.temperature = temperature
        self.top_k, self.top_p = top_k, top_p
        # 'auto' resolved ONCE, here, and said out loud (stderr: the bench
        # tools own stdout): which paged-attention lowering every program of
        # this engine compiles is never left to a silent backend test.
        self.attn_impl = resolve_paged_impl(attn_impl)
        print(
            f"ServeEngine: paged attention impl={self.attn_impl!r} "
            f"(requested {attn_impl!r}, backend {jax.default_backend()!r})",
            file=sys.stderr,
        )
        # Split-K policy (docs/SERVING.md "Split-K decode"): "auto" picks a
        # per-round pow2 split from the page bucket (_split_bucket) — short
        # traffic resolves to 1 and compiles/runs the classic unsplit
        # program; an int forces that split for every round (tests). Like
        # the page bucket and the mesh, the resolved split is a trailing
        # static jit arg: each (bucket, split) pair is its own compile-cache
        # entry, and split programs never perturb unsplit ones.
        if split_k != "auto" and (not isinstance(split_k, int) or split_k < 1):
            raise ValueError(f"split_k must be 'auto' or a positive int, got {split_k!r}")
        self.split_k = split_k
        # Round-overlap dispatch (docs/SERVING.md "Round-overlap dispatch"):
        # "off" keeps the classic settle-every-round loop byte-identical;
        # "group" fuses round_group decode rounds into one dispatched
        # program (settled at the group edge, same step order otherwise);
        # "double" additionally keeps ONE group in flight while the host
        # phases of the previous round run (_step_overlapped). Both modes
        # share _serve_decode_group, so flipping between them after warmup
        # compiles nothing (tests/test_recompile_pins.py). Speculative
        # engines ignore "double"/"group" for their spec rounds — a
        # draft-then-verify round is already two fused dispatches with a
        # host commit between, and overlapping it would re-order the
        # rollback against the next draft — and run the classic step loop.
        if overlap not in ("off", "double", "group"):
            raise ValueError(
                f"overlap must be 'off', 'double' or 'group', got {overlap!r}"
            )
        self.overlap = overlap
        self.round_group = _round_group_bucket(round_group)
        self._inflight: tp.Optional[_InflightRound] = None
        # Killed in-flight overlapped groups (kill_overlapped_round chaos).
        self.overlap_kills = 0
        # (round, (uid, ...)) per decode dispatch — the deferred-effect
        # observability hook: tests assert a request admitted/evicted
        # during round N's host phase first appears/disappears in round
        # N+2's dispatch (the one-round-late policy boundary).
        self.dispatch_log: tp.Deque[tp.Tuple[int, tp.Tuple[int, ...]]] = (
            collections.deque(maxlen=256)
        )
        self.cache_dtype = normalize_cache_dtype(cache_dtype)
        # Backpressure bound: worst-case page demand (prompt + full budget)
        # summed over every live request, queued or running. None (default):
        # admission is unbounded, the pre-TTL behavior.
        self.max_backlog_pages = max_backlog_pages
        # Cross-request prefix sharing (module docstring; default OFF so a
        # plain engine's scheduling is bit-for-bit the pre-trie behavior).
        self.prefix_cache = PrefixCache(page_size) if prefix_cache else None
        # prefix-cache counters (prefix_stats): matched vs structurally
        # matchable prompt tokens per admission, COW tail re-prefills,
        # trie pages reclaimed under allocator pressure, and total prompt
        # tokens actually pushed through prefill chunks (the r10
        # self-re-prefill regression pin reads this one).
        self._prefix_matched_tokens = 0
        self._prefix_matchable_tokens = 0
        self.cow_pages = 0
        self.prefix_evictions = 0
        self.prefilled_tokens = 0
        self.prefill_chunks = 0  # slot-chunks prefilled
        self.prefill_calls = 0  # prefill programs enqueued (chunks / calls rode each)
        self.first_tokens = 0  # first tokens taken from the prefill program's sample
        self.first_logit_pulls = 0  # calls whose logits came to the host (on_first_logits)
        # Host-RAM KV spill tier (sampling/fleet.py SpillTier), wired by
        # attach_spill: evicted trie pages land there instead of being
        # discarded, and _admit re-adopts resident runs past the trie
        # match. None (default): evictions discard, the pre-fleet
        # behavior.
        self.spill_tier = None
        self.spill_readopted_pages = 0
        self.spill_readopt_events = 0
        # ---- speculative decoding (docs/SERVING.md) ----
        # A draft model turns every decode round into draft-k-then-verify:
        # the draft proposes spec_k tokens against its OWN paged pool, the
        # target scores them in one verify forward, and a rejection sampler
        # keeps the longest valid prefix (+1 corrected/bonus token). The
        # draft pool shares the page table and allocator with the target —
        # one logical page maps to the same physical index in both pools —
        # so the scheduler stays single-track.
        if (draft_params is None) != (draft_config is None):
            raise ValueError("draft_params and draft_config come together")
        if draft_config is not None:
            if draft_config.block_size != config.block_size:
                raise ValueError(
                    f"draft block_size {draft_config.block_size} != target "
                    f"{config.block_size} — the shared page table assumes "
                    "equal position spaces"
                )
            for k_name, k_val in (("spec_k_max", spec_k_max),
                                  ("spec_k_min", spec_k_min)):
                if k_val < 1 or k_val & (k_val - 1):
                    raise ValueError(f"{k_name}={k_val} must be a power of two")
            if spec_k_min > spec_k_max:
                raise ValueError(
                    f"spec_k_min={spec_k_min} > spec_k_max={spec_k_max}"
                )
            if draft_shares_cache and (
                draft_config.n_head != config.n_head
                or draft_config.head_dim != config.head_dim
                or draft_config.n_layer >= config.n_layer
            ):
                raise ValueError(
                    "draft_shares_cache requires a layer-prefix draft: same "
                    "n_head/head_dim, fewer layers (sampling/spec.py "
                    "self_draft)"
                )
        self.draft_params = draft_params
        self.draft_config = draft_config
        self.draft_shares_cache = draft_shares_cache
        self.spec_k_max = spec_k_max
        self.spec_k_min = spec_k_min
        self.spec_adapt = spec_adapt
        # The paged pool (sampling/pages.py): one pool, one allocator and one
        # page table a kind of cache, the draft model's pool beside the first
        # where it has one of its own, and the books of all of them.
        self.pool = PagePool(
            config, max_slots=max_slots, num_pages=num_pages, pool_hbm_bytes=pool_hbm_bytes,
            page_size=page_size, burst=max(prefill_chunk, decode_chunk * self.round_group),
            cache_dtype=self.cache_dtype, kernel_layout=self.attn_impl == "kernel",
            prefill_width=self.prefill_width, mesh=mesh, prefix_cache=self.prefix_cache,
            draft_config=draft_config, draft_shares_cache=draft_shares_cache,
        )
        # aggregate speculative counters (spec_stats)
        self._spec_rounds = 0
        self._spec_verifies = 0  # (slot, round) pairs
        self._spec_drafted = 0
        self._spec_accepted = 0
        self.slots: tp.List[tp.Optional[_Slot]] = [None] * max_slots
        self.queue: tp.List[Request] = []
        self.finished: tp.Dict[int, FinishedRequest] = {}
        # The sampling key lives on the DEVICE and the host never splits it:
        # every sampled program takes it, splits its own key(s) off it first
        # thing (`_split_key`) and hands back the one kept here, unforced, for
        # the next program (`_sampling_key`). A dispatch is then ONE jit call:
        # the round's other arguments (tokens, lengths, masks, page tables)
        # are numpy and ride that call's own argument handling, so nothing
        # else is dispatched to the device on the path of a round. A greedy
        # engine's programs take no key and hand None back: it has no use
        # for this one.
        self._key = jax.random.PRNGKey(seed)
        self._uid = 0
        self._admitted = 0
        # Recompute-style preemptions since construction (one per _evict):
        # the oversubscription cost a byte budget trades against — int8
        # mode's 2x pages shows up here as strictly fewer evictions on the
        # same trace (tests/test_quant_cache.py).
        self.preemptions = 0
        # Robustness/SLO counters (reported by stats() and the chaos
        # serve scenarios): scheduling rounds, deadline timeouts,
        # admission sheds, client cancellations, and killed decode rounds.
        self.rounds = 0
        # Jit calls enqueued on the serving path, every one of the six
        # programs of this module (`_call_mark`): the number a call takes is
        # its `call`, and execution k of the engine's programs on the device
        # is dispatch k (docs/OBSERVABILITY.md "A dispatch on both clocks").
        self.dispatches = 0
        self.timeouts = 0
        self.shed = 0
        self.cancelled = 0
        self.decode_kills = 0
        # uids whose pool pages were corrupted by the poisoned_page fault —
        # the slots a chaos parity check must exclude (everyone else's
        # stream never reads the poisoned physical page).
        self.poisoned_uids: tp.List[int] = []
        # ---- zero-downtime model ops (sampling/ops.py) ----------------
        # weights_version identifies which weights serve each round on
        # stats() and flight-recorder dumps: "<step>:<sha12>" for verified
        # checkpoints (training/checkpoint.py weights_version) or "inline"
        # for directly-passed params. A staged blue/green swap pauses
        # admissions (so queued arrivals deterministically take the NEW
        # weights) and flips at the first slot-free round boundary.
        self.weights_version = weights_version
        self.hot_swaps = 0
        self.resizes = 0
        self.swap_history: tp.List[tp.Dict[str, tp.Any]] = []
        self.resize_history: tp.List[tp.Dict[str, tp.Any]] = []
        self._staged_swap: tp.Optional[tp.Dict[str, tp.Any]] = None
        # Uids that have been recompute-preempted at least once: a queued
        # entry with one of these uids is a stream ALREADY in flight (its
        # early tokens are committed), not a fresh arrival — the staged-
        # swap admission pause must let it resume on the old weights, and
        # the flip must wait for it (sampling/ops.py). Uids are never
        # reused, so the set is grow-only.
        self._resumed_uids: tp.Set[int] = set()
        # Chaos hooks (robustness/chaos_serve.py): hot_swap_mid_decode
        # pulls its payload from swap_source (a callable returning
        # hot_swap kwargs incl. "params"); pool_resize pops its next
        # num_pages target from resize_plan. Both None/empty in production.
        self.swap_source: tp.Optional[tp.Callable[[], tp.Dict[str, tp.Any]]] = None
        self.resize_plan: tp.List[int] = []

    # -- public surface ------------------------------------------------

    # What the pool owns, as the engine's callers read it (benchmarks/, tests).
    # `cache` is also ASSIGNED, by every dispatch: a serving program is handed
    # the pool (donated) and hands back the next one.

    @property
    def cache(self):
        return self.pool.cache

    @cache.setter
    def cache(self, cache) -> None:
        self.pool.cache = cache

    @property
    def allocators(self) -> tp.List[PageAllocator]:
        return self.pool.allocators

    @property
    def allocator(self) -> PageAllocator:
        """The first kind's allocator: what every mechanism that knows one
        kind of page reads."""
        return self.pool.allocators[0]

    @property
    def max_pages_per_slot(self) -> int:
        return self.pool.max_pages_per_slot

    def cache_hbm_bytes(self) -> int:
        return self.pool.hbm_bytes()

    def cache_hbm_bytes_per_shard(self) -> int:
        return self.pool.hbm_bytes_per_shard()

    def submit(
        self,
        prompt: tp.Sequence[int],
        max_new_tokens: int,
        eos_id: tp.Optional[int] = None,
        ttl_s: tp.Optional[float] = None,
    ) -> int:
        """Queue a request. `ttl_s` bounds its total residence time: a
        request still unfinished `ttl_s` seconds from now is evicted with a
        `timeout` status instead of occupying queue slots / pool pages
        forever. Raises BackpressureError when the scheduler policy sheds
        the request (over the `max_backlog_pages` budget, or — SLOScheduler
        — an already-infeasible deadline)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        S = self.config.block_size
        if len(prompt) + max_new_tokens > S:
            # The paged pool is sized to the trained context; the windowed
            # overflow scheme of engine.generate has no incremental cache to
            # page. Reject instead of silently truncating.
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds block_size ({S})"
            )
        need = -(-(len(prompt) + max_new_tokens) // self.page_size)
        if need > self.allocator.num_pages - 1:
            raise ValueError(
                f"request needs {need} pages but the pool only has "
                f"{self.allocator.num_pages - 1} allocatable"
            )
        now = self._clock()
        deadline = None if ttl_s is None else now + ttl_s
        shed = self.scheduler.shed_reason(need, deadline, self, now)
        if shed is not None:
            message, retryable = shed
            self.shed += 1
            self._trace.instant(
                "shed", "lifecycle", self._obs_tid,
                args={"needed_pages": need, "retryable": retryable},
            )
            raise BackpressureError(
                message,
                needed_pages=need,
                backlog_pages=self._backlog_pages(),
                budget_pages=self.max_backlog_pages,
                retryable=retryable,
            )
        uid = self._uid
        self._uid += 1
        self.queue.append(
            Request(uid, prompt, max_new_tokens, eos_id, deadline, now)
        )
        if self.obs is not None:
            self._req_phase(uid, "req.queue", now)
        return uid

    def _req_phase(
        self, uid: int, phase: tp.Optional[str], t: float,
        end_args: tp.Optional[dict] = None,
        begin_args: tp.Optional[dict] = None,
    ) -> None:
        """A request's life as async tracks (id = uid) and histograms:
        `req.queue` (submit or preemption -> admitted), `req.prefill`
        (admitted -> first token appended), `req.decode` (first -> last
        token). Closes the request's open phase at `t` and opens `phase`
        (None: the request is over). Called with obs ON only, with clock
        readings the engine took anyway — `t` is what `on_token` hands the
        client, so queue + prefill IS the client's time to first token.
        Async, never "X": a seconds-long complete span per request would
        own every idle gap under it (benchmarks/reduce.py attribute_gaps)."""
        was = self._req_open.pop(uid, None)
        if was is not None:
            name, t0 = was
            self.obs.req_phase_s[name].observe(t - t0)
            self._trace.async_end(
                name, uid, "request", self._obs_tid, end_args, t
            )
        if phase is not None:
            self._req_open[uid] = (phase, t)
            self._trace.async_begin(
                phase, uid, "request", self._obs_tid, begin_args, t
            )

    def _backlog_pages(self) -> int:
        """Worst-case page demand committed to live (queued + running)
        requests. Uses each request's FULL footprint — prompt plus the whole
        generation budget — because that is what the pool must eventually
        absorb if nothing times out early.

        With the prefix cache on the accounting is refcount-aware: a shared
        page is charged ONCE (the trie's referenced-entry count) instead of
        once per reader — each running slot subtracts its n_shared and each
        queued request subtracts what it would currently match (a ref-free
        `peek`). Refcount-0 trie pages are charged nothing: they are
        reclaimed on demand before any preemption, so they never stand
        between an admission and its pages. Cache off: identical to the
        pre-trie arithmetic."""

        def worst(req: Request) -> int:
            return -(-(len(req.prompt) + req.max_new_tokens) // self.page_size)

        pc = self.prefix_cache
        queued = sum(
            worst(r)
            - (0 if pc is None else pc.peek(r.prompt, max_tokens=len(r.prompt) - 1))
            for r in self.queue
        )
        running = sum(
            worst(s.request) - s.n_shared for s in self.slots if s is not None
        )
        shared = 0 if pc is None else pc.referenced_page_count()
        return queued + running + shared

    @property
    def idle(self) -> bool:
        # A staged hot-swap counts as pending work: the drive loop must
        # keep stepping until the flip lands (sampling/ops.py), or a swap
        # staged on a draining engine would never complete. Likewise an
        # unsettled in-flight decode group (overlap="double"): its tokens
        # are not committed until the next step settles it, so the drive
        # loop must take one more step even if every slot just drained.
        return (
            not self.queue
            and all(s is None for s in self.slots)
            and self._staged_swap is None
            and self._inflight is None
        )

    def run(self) -> tp.Dict[int, FinishedRequest]:
        """Drive step() until everything submitted so far has finished."""
        while not self.idle:
            self.step()
        return self.finished

    def cancel(self, uid: int, status: str = "cancelled") -> bool:
        """Finish a queued or running request NOW: its pages return to the
        pool, its partial tokens are recorded under `status`, and no other
        slot is touched — cancellation must never perturb a co-resident
        request's stream (pinned with the page-conservation invariant in
        tests/test_serving.py). A request preempted earlier returns its
        re-queued prompt (generated tokens folded in). False if `uid` is
        unknown or already finished. Call between rounds only (the engine
        is single-threaded host code; the async server serializes its
        cancellations onto the driver loop)."""
        for qi, req in enumerate(self.queue):
            if req.uid == uid:
                self.queue.pop(qi)
                self.cancelled += 1
                self._finish(
                    FinishedRequest(
                        uid=uid, tokens=req.prompt, token_times=[],
                        status=status,
                    )
                )
                return True
        for i, slot in enumerate(self.slots):
            if slot is not None and slot.request.uid == uid:
                self.cancelled += 1
                self._retire(i, slot, status)
                return True
        return False

    def _retire(self, slot_i: int, slot: "_Slot", status: str, t: tp.Optional[float] = None) -> None:
        """The one way a RUNNING slot's request ends (finished, EOS, timeout,
        cancelled): its tokens are recorded under `status`, its pages go
        through `_release_slot`, the slot is free. `t`: as `_finish`."""
        self._finish(
            FinishedRequest(
                uid=slot.request.uid,
                tokens=np.concatenate(
                    [slot.request.prompt, np.asarray(slot.generated, np.int32)]
                ),
                token_times=slot.token_times,
                status=status,
            ),
            t,
        )
        self._release_slot(slot)
        self.slots[slot_i] = None

    def _refuse_several_kinds(self, mechanism: str) -> None:
        """What is not wired for a family whose layers need several kinds of
        cache, or a STATE kind (a row a slot: what moves, shares, snapshots or
        quantises pages knows nothing of it), stops here, by mechanism (the
        constructor; hot_swap, resize, attach_spill; sampling/disagg.py)."""
        family = getattr(self.config, "family", "gpt")
        if len(self.kinds) > 1:
            raise NotImplementedError(
                f"ServeEngine: {mechanism} is not wired for a model with "
                f"{len(self.kinds)} kinds of paged cache "
                f"({', '.join(k.name for k in self.kinds)}: family "
                f"{family!r})"
            )
        if self.state_kinds:
            raise NotImplementedError(
                f"ServeEngine: {mechanism} is not wired for a model with a STATE kind of cache "
                f"({', '.join(k.name for k in self.state_kinds)}: a row a slot and not a page a token; family {family!r})"
            )

    def hot_swap(
        self,
        params: GPTParams,
        *,
        draft_params: tp.Optional[GPTParams] = None,
        version: str = "inline",
        config: tp.Optional[GPTConfig] = None,
    ) -> tp.Dict[str, tp.Any]:
        """Stage a blue/green weight swap; flips at the first slot-free
        round boundary (immediately when idle). Same-shape swaps compile
        ZERO new programs; mismatches raise a structured HotSwapError
        before anything changes. Full protocol: sampling/ops.py,
        docs/ROBUSTNESS.md "Zero-downtime model ops"."""
        from midgpt_tpu.sampling import ops as _ops

        self._refuse_several_kinds("a staged weight swap (fleet hot-swap)")
        return _ops.stage_hot_swap(
            self, params, draft_params=draft_params, version=version,
            config=config,
        )

    def resize(
        self,
        num_pages: tp.Optional[int] = None,
        *,
        max_slots: tp.Optional[int] = None,
    ) -> tp.Dict[str, tp.Any]:
        """Live pool resize: migrate the resident working set into a fresh
        `num_pages` pool (int8 scales ride along), remap slots + trie, and
        install a new allocator. Shrinking below the resident working set
        raises a retryable PoolResizeError instead of dropping live data
        (sampling/ops.py)."""
        from midgpt_tpu.sampling import ops as _ops

        self._refuse_several_kinds("a live pool resize")
        # A resize migrates the resident working set out of self.cache —
        # an unsettled in-flight group still writing into the OLD pool
        # must land (and its tokens commit) before the migration reads it.
        self._settle_inflight()
        return _ops.resize_pool(self, num_pages, max_slots=max_slots)

    def attach_spill(self, tier) -> None:
        """Wire a host-RAM spill tier (sampling/fleet.py SpillTier) under
        the prefix trie: every refcount-0 eviction — allocator pressure,
        forced flush, resize overflow, disagg adopt-side reclaim — lands
        the page's content in `tier` keyed by its full token prefix
        (PrefixCache.on_evict) instead of discarding it, stamped with the
        CURRENT weights_version so a hot swap can never resurrect
        old-weights KV. Requires the prefix cache: the trie is both the
        spill source and the re-adoption anchor."""
        self._refuse_several_kinds("the host-RAM spill tier")
        if self.prefix_cache is None:
            raise ValueError("attach_spill requires prefix_cache=True")
        tier.set_page_size(self.page_size)
        self.spill_tier = tier
        self.prefix_cache.on_evict = lambda prefix, page: tier.spill(
            self.cache, prefix, page, self.weights_version
        )

    def _readopt_from_spill(self, slot: "_Slot", req: "Request") -> None:
        """Extend an admission's trie match with spilled pages: consult
        the tier for a resident run starting exactly where the match
        stopped, allocate plainly (a spill hit is an optimization, never
        a demand — it must not evict trie pages or preempt anyone),
        checksum-verify and move the run out of the tier, scatter it into
        the pool (pages.adopt_pages: the one page-transport funnel), and
        start the slot
        committed past it. The re-adopted pages are PRIVATE until prefill
        completion, when insert_live shares them like any other complete
        prompt pages. A checksum or weights_version mismatch truncates
        the run inside take_run and those tokens simply re-prefill —
        corrupt spill bytes can never reach a decode."""
        tier = self.spill_tier
        ps = self.page_size
        start = len(slot.pages[0])
        limit = (len(req.prompt) - 1) // ps - start
        if limit <= 0:
            return
        n = tier.peek_run(req.prompt, start, limit, self.weights_version)
        if n == 0:
            return
        n = min(n, self.allocator.free_count)  # plain alloc: take what's free
        if n == 0:
            return
        got = self.pool.alloc(0, n)
        if got is None:
            return
        blocks_list = tier.take_run(req.prompt, start, n, self.weights_version)
        m = len(blocks_list)
        if m == 0:
            self.pool.free(0, got)
            return
        if m < n:
            self.pool.free(0, got[m:])
            got = got[:m]
        with self._trace.span("spill.readopt", "prefix", self._obs_tid):
            self.cache = adopt_pages(self.mesh, self.cache, got, join_pages(blocks_list))
        slot.pages[0].extend(got)
        slot.prompt_pos = slot.length = (start + m) * ps
        self._prefix_matched_tokens += m * ps  # a cross-tier hit is a hit
        self.spill_readopted_pages += m
        self.spill_readopt_events += 1
        self._trace.instant(
            "spill.hit", "prefix", self._obs_tid,
            args={"uid": req.uid, "pages": m},
        )

    def _hot_swap_fault(self) -> None:
        """The `hot_swap_mid_decode` chaos fault: stage whatever weights
        the scenario registered on `swap_source` at this round boundary —
        the production swap path end to end, just triggered by the fault
        registry instead of an operator (robustness/chaos_serve.py)."""
        if self.swap_source is None:
            return
        payload = dict(self.swap_source())
        self.hot_swap(payload.pop("params"), **payload)

    def _pool_resize_fault(self) -> None:
        """The `pool_resize` chaos fault: resize to the next target on
        `resize_plan` (e.g. [43, 37] for a grow-then-shrink gate)."""
        if not self.resize_plan:
            return
        self.resize(self.resize_plan.pop(0))

    @staticmethod
    def compile_stats(census: bool = False) -> tp.Dict[str, tp.Any]:
        """Compiled-program census of the serving jits (graftcheck pass-2
        hook). The scheduling claim in the module docstring — page tables
        and lengths are plain jit inputs, so admitting/finishing requests
        never recompiles — is only as good as these numbers staying flat:
        `decode` is bounded by |{(n_steps, page bucket)}|, `prefill` by
        |{page bucket}|, regardless of request mix. Pinned by
        tests/test_recompile_pins.py. Process-global (module-level jits shared by every engine).

        `pool_relayouts` is the kernel path's layout census, per compiled
        program: pool- or layer-sized copies in its compiled text, 0 when
        the pool keeps one layout from parameter to result (PagedKVCache
        "Layout contract"). `weight_copies`, beside it: instructions that
        write a matrix of a stacked parameter out again, 0 when every matmul
        reaches such a weight where it lies (utils/hlo.weight_copies; the
        GPT's layer loop since PR 62. Its `rope_style="split"` rebuilds the q
        and k thirds of every layer in the program, and those count: 38 in a
        12-layer program). Both hold what has
        been read so far; `census=True` (sample.py's exit table) reads the
        text of every program compiled since, a fraction of a second each,
        so it is not for the serving loop. Empty on the XLA path."""
        programs = {
            "prefill": _serve_prefill_chunk,
            "decode": _serve_decode_chunk,
            "decode_logits": _serve_decode_logits,
            "decode_group": _serve_decode_group,
            "spec_draft": _spec_draft_chunk,
            "spec_verify": _spec_verify_chunk,
        }
        stats: tp.Dict[str, tp.Any] = {
            name: jit_cache_size(p) for name, p in programs.items()
        }
        if census:
            for p in programs.values():
                p.census()
        stats["pool_relayouts"] = {
            label: n for p in programs.values() for label, n in p._relayouts.items()
        }
        stats["weight_copies"] = {
            label: n for p in programs.values() for label, n in p._weight_copies.items()
        }
        return stats

    @staticmethod
    def program_texts() -> tp.Dict[str, str]:
        """{serving program as compiled: its optimized HLO text}
        (as `TrainRuntime.step_program_text()` for the step): what a reader of
        a device trace joins an op's instruction name to the `named_scope`
        that opened it with. Process-global like `compile_stats`."""
        return {
            label: text
            for p in (_serve_prefill_chunk, _serve_decode_chunk, _serve_decode_group)
            for label, text in p.texts().items()
        }

    def mesh_shape(self) -> tp.Optional[tp.Dict[str, int]]:
        """{'data': d, 'tp': t} when mesh-sharded, None single-chip."""
        from midgpt_tpu.parallel.serve_tp import mesh_shape

        return mesh_shape(self.mesh)

    def stats(self) -> tp.Dict[str, tp.Any]:
        """Deployment-shape + counter snapshot for SLO reporting: a sharded
        run is distinguishable from a single-chip one by its record alone."""
        return {
            "mesh": self.mesh_shape(),
            "cache_hbm_bytes": self.cache_hbm_bytes(),
            "cache_hbm_bytes_per_shard": self.cache_hbm_bytes_per_shard(),
            "rounds": self.rounds,
            "dispatches": self.dispatches,
            "overlap_mode": self.overlap,
            "round_group": self.round_group,
            "overlap_kills": self.overlap_kills,
            "preemptions": self.preemptions,
            "first_tokens": self.first_tokens,
            "first_logit_pulls": self.first_logit_pulls,
            "timeouts": self.timeouts,
            "shed": self.shed,
            "cancelled": self.cancelled,
            "weights_version": self.weights_version,
            "hot_swaps": self.hot_swaps,
            "resizes": self.resizes,
            "spill_readopted_pages": self.spill_readopted_pages,
            "spill_readopt_events": self.spill_readopt_events,
            "window_reclaimed_pages": self.pool.kind_reclaimed[0],
            "swap_pending": self._staged_swap is not None,
            "compile_counts": self.compile_stats(),
            # unified observability schema (docs/OBSERVABILITY.md): round
            # decomposition + metrics when an Observability is wired in,
            # {"enabled": False} otherwise — consumers key on the flag.
            # The cache's and the family's counters ride in it.
            "obs": (
                DISABLED_SNAPSHOT
                if self.obs is None
                else {**self.obs.snapshot(), **self.serve_counters()}
            ),
        }

    def serve_counters(self) -> tp.Dict[str, float]:
        """Counters of the cache by kind (`PagePool.counters`) and of the
        family's own layers (docs/OBSERVABILITY.md), whether or not an
        Observability is wired: what the family's `serve_counters` reads off its
        cache is a device read: call it between rounds, not in them."""
        out = self.pool.counters()
        if self.model.serve_counters is not None:
            out.update(self.model.serve_counters(self.config, self.cache))
        return out

    # -- scheduling round ----------------------------------------------

    def step(self) -> None:
        """One round: expire -> admit -> one prefill chunk -> one decode
        chunk (or one draft-then-verify speculative round).

        The serving fault hooks fire here (robustness/faults.py; an
        empty registry — the default, always — costs a scan over nothing).
        All are keyed on the ROUND counter so chaos scenarios are
        deterministic for a seeded trace (`kill_mid_decode@7` always
        strikes round 7).

        With overlap="double" (and no draft model) the round runs the
        RESTRUCTURED order of `_step_overlapped` instead: dispatch this
        round's decode group FIRST, then settle the previous round and run
        every host phase while the new group computes on the device.
        With overlap="group" the order below is unchanged — only the
        decode call fuses `round_group` rounds into one dispatch.

        Either order runs inside one `engine.round` span: the phases and
        the decode decomposition nest under it (the tracer records the
        parent), so its self time is the scheduler's own cost."""
        chunks0, calls0 = self.prefill_chunks, self.prefill_calls
        with self._trace.span("engine.round", "round", self._obs_tid) as sp:
            if self.overlap == "double" and self.draft_params is None:
                self._step_overlapped()
            else:
                self._step_classic()
        if self.obs is not None:
            self.obs.record_engine_round(
                sp.dur, self.prefill_chunks - chunks0, self.prefill_calls - calls0,
                sum(s is not None and not s.prefilling for s in self.slots),
            )

    def _step_classic(self) -> None:
        self.rounds += 1
        tr = self._trace
        if faults.should_fire("poisoned_page", step=self.rounds):
            tr.instant("fault.poisoned_page", "fault", self._obs_tid)
            self._poison_page()
        if faults.should_fire("evict_shared_prefix", step=self.rounds):
            tr.instant("fault.evict_shared_prefix", "fault", self._obs_tid)
            self._evict_shared_prefix_fault()
        if faults.should_fire("hot_swap_mid_decode", step=self.rounds):
            tr.instant("fault.hot_swap_mid_decode", "fault", self._obs_tid)
            self._hot_swap_fault()
        if faults.should_fire("pool_resize", step=self.rounds):
            tr.instant("fault.pool_resize", "fault", self._obs_tid)
            self._pool_resize_fault()
        with tr.span("engine.expire", "phase", self._obs_tid):
            self._expire_round()
        if self._staged_swap is not None:
            # Blue/green flip point: after expiry (slots may have just
            # drained), before admission (which is paused while staged).
            from midgpt_tpu.sampling import ops as _ops

            _ops.maybe_flip_swap(self)
        with tr.span("engine.admit", "phase", self._obs_tid):
            self._admit()
        with tr.span("engine.prefill", "phase", self._obs_tid):
            self._prefill_round()
        if faults.should_fire("kill_mid_decode", step=self.rounds):
            tr.instant("fault.kill_mid_decode", "fault", self._obs_tid)
            self._kill_decode_round()
        elif self.draft_params is not None:
            self._spec_round()
        elif self.overlap == "group":
            self._decode_round_grouped()
        else:
            self._decode_round()

    def _step_overlapped(self) -> None:
        """One DOUBLE-BUFFERED round (overlap="double"): dispatch round
        k's decode group FIRST — chaining device-side token/length state
        from the still-unsettled round k-1 — then settle round k-1 and run
        every host phase (expire, swap flip, admission, prefill) while
        round k's program runs on the device. The settle's force waits
        only for round k-1, never for round k, so round k-1's host
        post-processing is HIDDEN under round k's device time — the
        `overlap_hidden_ms` measure (obs/__init__.py).

        The restructured order is what makes scheduler effects one round
        late BY CONSTRUCTION (docs/SERVING.md "Round-overlap dispatch"):
        round N's host phase runs here in step N+1, after dispatch
        D_{N+1} is already in flight, so a request admitted or evicted
        during it first appears/disappears in dispatch D_{N+2} — never
        mid-flight. Faults that mutate the pool or the engine shape
        (poisoned_page, evict_shared_prefix, hot_swap_mid_decode,
        pool_resize) assume a settled round boundary, so the in-flight
        group is drained before any of them strike."""
        self.rounds += 1
        tr = self._trace
        if self._inflight is not None and self._fault_needs_drain():
            self._settle_inflight()
        if self._inflight is not None and faults.should_fire(
            "kill_overlapped_round", step=self.rounds
        ):
            tr.instant("fault.kill_overlapped_round", "fault", self._obs_tid)
            self._kill_overlapped_round()
        if faults.should_fire("poisoned_page", step=self.rounds):
            tr.instant("fault.poisoned_page", "fault", self._obs_tid)
            self._poison_page()
        if faults.should_fire("evict_shared_prefix", step=self.rounds):
            tr.instant("fault.evict_shared_prefix", "fault", self._obs_tid)
            self._evict_shared_prefix_fault()
        if faults.should_fire("hot_swap_mid_decode", step=self.rounds):
            tr.instant("fault.hot_swap_mid_decode", "fault", self._obs_tid)
            self._hot_swap_fault()
        if faults.should_fire("pool_resize", step=self.rounds):
            tr.instant("fault.pool_resize", "fault", self._obs_tid)
            self._pool_resize_fault()
        if faults.should_fire("kill_mid_decode", step=self.rounds):
            # This round's dispatch dies: settle the previous group (its
            # tokens landed before the failure), then recompute-preempt
            # the decode-ready slots exactly like the classic path.
            tr.instant("fault.kill_mid_decode", "fault", self._obs_tid)
            self._settle_inflight()
            self._kill_decode_round()
            handle = None
        else:
            handle = self._dispatch_decode(self._inflight)
        prev, self._inflight = self._inflight, handle
        if prev is not None:
            self._settle_round(prev)
        with tr.span("engine.expire", "phase", self._obs_tid):
            self._expire_round()
        if self._staged_swap is not None:
            # The flip reads/replaces engine weights and waits for a
            # slot-free boundary — an unsettled group is pending work the
            # drain must observe, so settle before consulting it.
            self._settle_inflight()
            from midgpt_tpu.sampling import ops as _ops

            _ops.maybe_flip_swap(self)
        with tr.span("engine.admit", "phase", self._obs_tid):
            self._admit()
        with tr.span("engine.prefill", "phase", self._obs_tid):
            self._prefill_round()

    def _kill_decode_round(self) -> None:
        """The `kill_mid_decode` fault: this round's decode dispatch died
        (device restart mid-dispatch) and its tokens never landed. Recovery
        is the eviction machinery the engine already trusts: every
        decode-ready slot is recompute-preempted — pages freed, generated
        tokens folded into the prompt, re-queued oldest-first — so the
        requests re-prefill and continue with token streams identical to an
        unfaulted run (greedy recompute parity is pinned by
        tests/test_serving.py::test_serve_parity_under_eviction and
        asserted end to end by the chaos gate, tests/test_chaos_serve.py).
        Mid-prefill slots are untouched: the fault models the DECODE
        program dying, and prefill chunks already landed."""
        victims = [
            s
            for s in self.slots
            if s is not None and not s.prefilling and s.remaining > 0
        ]
        # Youngest evicts first: each _evict inserts at the queue FRONT, so
        # reverse admit order leaves the queue oldest-first for re-admission.
        for s in sorted(victims, key=lambda s: s.admit_order, reverse=True):
            self._evict(s)
        self.decode_kills += 1

    # -- round-overlap dispatch (docs/SERVING.md) ----------------------

    # Faults that mutate the pool or the engine's shape mid-round; each
    # assumes a settled round boundary, so an in-flight overlapped group
    # is drained before any of them fires (_step_overlapped).
    _DRAIN_FAULTS = (
        "poisoned_page",
        "evict_shared_prefix",
        "hot_swap_mid_decode",
        "pool_resize",
    )

    def _fault_needs_drain(self) -> bool:
        """Peek (without consuming) whether a boundary-assuming fault can
        fire this round — `faults.active()` is a copy, `should_fire` later
        in the step still performs the one consuming match."""
        for f in faults.active():
            if (
                f.kind in self._DRAIN_FAULTS
                and f.times > 0
                and (f.step is None or f.step == self.rounds)
            ):
                return True
        return False

    def _force(self, fn: tp.Callable[[], tp.Any], label: str) -> tp.Any:
        """Route a host<->device force through the watchdog when armed —
        the ONE funnel every decode-path sync takes, so a hang inside an
        overlapped in-flight dispatch escalates exactly like a classic
        round's (robustness/watchdog.py)."""
        if self.watchdog is not None:
            return self.watchdog.sync(fn, label=label)
        return fn()

    def _settle_inflight(self) -> None:
        """Settle the in-flight group now, if any (drain point for mode
        flips, pool mutations, and engine teardown paths)."""
        h, self._inflight = self._inflight, None
        if h is not None:
            self._settle_round(h)

    def _kill_overlapped_round(self) -> None:
        """The `kill_overlapped_round` fault: the in-flight group's
        dispatch died while the previous round's host work ran (device
        restart with TWO rounds in the pipe). Its tokens
        never land — the handle is dropped WITHOUT forcing — and every
        slot that was in the killed batch is recompute-preempted, the
        same recovery (and the same greedy-parity guarantee) as
        kill_mid_decode — pinned end to end by tests/test_chaos_serve.py
        ::test_chaos_kill_overlapped_round_recompute_parity. Slots that
        already departed are skipped; bystanders (mid-prefill slots,
        other streams) are untouched."""
        h, self._inflight = self._inflight, None
        if h is None:
            return
        self.overlap_kills += 1
        victims = [
            s
            for idx, s in zip(h.active_idx, h.slots)
            if self.slots[idx] is s and s.remaining > 0
        ]
        for s in sorted(victims, key=lambda s: s.admit_order, reverse=True):
            self._evict(s)

    def _decode_round_grouped(self) -> None:
        """overlap="group": one fused multi-round dispatch, settled at
        the group edge within the same step (no in-flight carry-over)."""
        h = self._dispatch_decode(None)
        if h is not None:
            self._settle_round(h)

    def _dispatch_decode(
        self, prev: tp.Optional[_InflightRound]
    ) -> tp.Optional[_InflightRound]:
        """Assemble and ENQUEUE one multi-round decode group without
        forcing it; returns the in-flight handle (None when nothing can
        decode). `prev` is the still-unsettled previous group under
        double-buffering: its slots are CHAINED — their true token/length
        state rides in on the previous program's unforced outputs and is
        merged in-program under `chain_mask`, so the host's one-round-
        stale view never reaches the device. Page provisioning for a
        chained slot budgets from its WORST-CASE post-settle length
        (prev.worst_len); if the pool can't cover a full group it falls
        back to one sub-round, and failing that the slot rides along
        masked (chained — the device takes zero steps for it) or defers
        to a later round (fresh)."""
        chained: tp.Set[int] = set()
        if prev is not None:
            chained = {
                idx
                for idx, s in zip(prev.active_idx, prev.slots)
                if self.slots[idx] is s
            }
        S = self.config.block_size
        ps = self.page_size

        def _want(s: _Slot) -> int:
            # The settle bound: at length P + max_new - 1 the request has
            # committed its full generation budget (_append_token's count).
            req = s.request
            return min(len(req.prompt) + req.max_new_tokens - 1, S)

        def _base(i: int, s: _Slot) -> int:
            return int(prev.worst_len[i]) if i in chained else s.length

        cand = []
        for i, s in enumerate(self.slots):
            if s is None or s.prefilling:
                continue
            if i not in chained and s.remaining <= 0:
                continue
            if _base(i, s) < _want(s):
                cand.append((i, s))
        if not cand:
            return None
        need = min(
            self.decode_chunk, max(_want(s) - _base(i, s) for i, s in cand)
        )
        n = 1 << (need.bit_length() - 1)  # largest power of two <= need
        T = n * self.round_group
        for i, slot in list(cand):
            if self.slots[i] is not slot:
                continue  # evicted by an older slot's growth in this loop
            upto = min(_want(slot), _base(i, slot) + T)
            if not self._ensure_pages(slot, upto):
                fallback = min(_want(slot), _base(i, slot) + n)
                if not self._ensure_pages(slot, fallback) and i not in chained:
                    # Pool held by slots at least as old — defer (classic
                    # _decode_round behavior). A chained slot keeps riding:
                    # its provisioned pages already cover worst_len, so
                    # max_len clamps it to zero steps, never to an overrun.
                    cand = [(j, t) for j, t in cand if j != i]
        cand = [(i, s) for i, s in cand if self.slots[i] is s]
        if not cand:
            return None

        obs = self.obs
        t0, c0 = (0.0, 0.0) if obs is None else (self._clock(), obs.cpu_clock())
        B = self.max_slots
        token = np.zeros((B,), np.int32)
        lengths = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        eos = np.full((B,), -1, np.int32)
        max_len = np.zeros((B,), np.int32)
        chain_mask = np.zeros((B,), bool)
        worst = np.zeros((B,), np.int32)
        for i, s in cand:
            token[i] = s.generated[-1] if s.generated else s.request.prompt[-1]
            lengths[i] = s.length
            active[i] = True
            if s.request.eos_id is not None:
                eos[i] = s.request.eos_id
            max_len[i] = min(_want(s), len(s.pages[0]) * ps)  # every kind's list has one logical length
            chain_mask[i] = i in chained
            worst[i] = min(_base(i, s) + T, max_len[i])
        round_span = int(worst.max())
        bucket = self._page_bucket(round_span)
        split_k = self._split_bucket(round_span)
        t_a = 0.0 if obs is None else self._clock()
        key = self._sampling_key()
        t_k = None if key is None or obs is None else self._clock()
        # Chain carry-in: the previous group's unforced outputs when
        # chaining, else zero fillers of the same shape/dtype — ONE
        # compiled program serves both cases, and nothing here syncs.
        if prev is not None:
            chain_token, chain_len = prev.tok_fin, prev.len_fin
        else:
            chain_token = np.zeros((B,), np.int32)
            chain_len = np.zeros((B,), np.int32)
        tables = self.pool.tables(self.slots, bucket)
        t_p = 0.0 if obs is None else self._clock()
        with self._call_mark():
            self.cache, toks, emitted, tok_fin, len_fin, self._key = _serve_decode_group(
                self.config,
                self.params,
                token,
                self.cache,
                tables,
                lengths,
                active,
                eos,
                max_len,
                chain_mask,
                chain_token,
                chain_len,
                n,
                self.round_group,
                self.temperature,
                self.top_k,
                self.top_p,
                self.attn_impl,
                key,
                self.mesh,
                split_k,
            )
        call = self.dispatches
        t1, c1 = (0.0, 0.0) if obs is None else (self._clock(), obs.cpu_clock())
        # lengths as the host holds them: a chained slot's trail the
        # device's by the previous group's steps (the count runs low there).
        # Counted after t1, while the device computes: not dispatch time.
        blocks = self._count_blocks(lengths, active, bucket, split_k, n_steps=T)
        self.dispatch_log.append(
            (self.rounds, tuple(s.request.uid for _, s in cand))
        )
        return _InflightRound(
            toks=toks,
            emitted=emitted,
            tok_fin=tok_fin,
            len_fin=len_fin,
            n_steps=T,
            active_idx=[i for i, _ in cand],
            slots=[s for _, s in cand],
            worst_len=worst,
            round_no=self.rounds,
            t0=t0,
            t1=t1,
            cuts=(t_a, t_k, t_p),
            # the group runs what its NEEDIEST slot wants and masks the rest
            limit="chunk" if need == self.decode_chunk else "need",
            call=call,
            bucket=bucket,
            cpu=(c0, c1),
            blocks=blocks,
        )

    def _settle_round(self, h: _InflightRound) -> None:
        """Force a dispatched group and commit its tokens. Indices whose
        slot object changed since dispatch (finished, evicted, cancelled,
        timed out) are SKIPPED — their in-flight tokens are discarded, and
        recompute preemption regenerates them bit-exactly. The force is
        the round's one host<->device sync, watchdog-bounded; under
        double-buffering the time between dispatch-return (h.t1) and this
        force starting is host work the overlap HID, recorded as
        `overlap_hidden` in the round decomposition (obs/__init__.py)."""
        obs = self.obs
        t_force = 0.0 if obs is None else self._clock()
        toks, emitted = self._force(
            lambda: (np.asarray(h.toks), np.asarray(h.emitted)),
            "serve.overlap_sync",
        )
        t_done = self._clock()
        if obs is not None:
            c_done = obs.cpu_clock()
            self._commit = [0, 0, 0.0]
        for idx, s in zip(h.active_idx, h.slots):
            if self.slots[idx] is not s:
                continue
            for j in range(h.n_steps):
                if not emitted[j, idx]:
                    continue
                s.length += 1
                if self._append_token(idx, s, int(toks[j, idx]), t_done):
                    break  # finished (max_new or EOS); rest discarded
        if obs is not None:
            obs.record_round(
                "decode", self._obs_tid, h.t0, h.t1, t_done, self._clock(),
                hidden_s=max(0.0, t_force - h.t1),
                cuts=h.cuts, steps=h.n_steps, slots=len(h.active_idx),
                chunk=self.decode_chunk * self.round_group, limit=h.limit,
                tokens=self._commit[0], finished=self._commit[1],
                callback_s=self._commit[2],
                call=h.call, bucket=h.bucket, blocks=h.blocks,
                cpu=(*h.cpu, c_done, obs.cpu_clock()),
            )

    def _poison_page(self) -> None:
        """The `poisoned_page` fault: corrupt the first page of the
        youngest running slot in place (NaN for float pools, saturated 127
        for int8), modeling HBM damage to committed K/V. No recovery is
        attempted — the point the chaos gate asserts is ISOLATION: page
        tables never alias live pages, so every other slot's tokens are
        bit-identical to an unfaulted run, the engine keeps serving, and
        the allocator stays conserved. The victim uid lands in
        `poisoned_uids` so chaos parity checks exclude exactly it
        (tests/test_chaos_serve.py pins the isolation claim). With the
        prefix cache on, the damaged page can be SHARED — every slot whose
        table maps it is marked (a future trie match of the page is out of
        scope for this fault: the poisoned_page chaos scenario runs
        cache-off, and the trie-specific fault is evict_shared_prefix)."""
        victim = max(
            (
                s
                for s in self.slots
                if s is not None and any(p >= 0 for p in s.pages[0])
            ),
            key=lambda s: s.admit_order,
            default=None,
        )
        if victim is None:
            return
        page = next(p for p in victim.pages[0] if p >= 0)
        self.pool.poison(page)
        for s in self.slots:
            if (
                s is not None
                and page in s.pages[0]
                and s.request.uid not in self.poisoned_uids
            ):
                self.poisoned_uids.append(s.request.uid)

    def _evict_shared_prefix_fault(self) -> None:
        """The `evict_shared_prefix` fault: a pressure spike (or an
        operator flush) force-reclaims EVERY unreferenced trie page at
        once, hot nodes included — ignoring the LRU order that normally
        protects them. What must hold, and what the chaos gate asserts
        (tests/test_chaos_serve.py): referenced entries survive — a shared
        node is never evicted out from under a live reader — so every live
        stream stays bit-identical to an unfaulted run; later requests
        simply miss the flushed prefixes, re-prefill, and re-populate the
        trie; and pages + refcounts stay conserved through the flush."""
        if self.prefix_cache is None:
            return
        freed = self.prefix_cache.evict(0, force_all=True)
        self.pool.free(0, freed)
        self.prefix_evictions += len(freed)

    def _expire_round(self) -> None:
        """Finish every deadline-expired request with a `timeout` status.

        Expired QUEUED requests stop blocking FCFS admission; expired
        RUNNING slots free their pages immediately — a stalled client
        deadline must not hold pool pages hostage while younger requests
        get evicted around it. Whatever tokens were generated before the
        deadline are returned (partial result)."""
        now = self._clock()

        def expired(req: Request) -> bool:
            return req.deadline is not None and now > req.deadline

        still_queued = []
        for req in self.queue:
            if expired(req):
                self.timeouts += 1
                self._finish(
                    FinishedRequest(
                        uid=req.uid, tokens=req.prompt, token_times=[],
                        status="timeout",
                    )
                )
            else:
                still_queued.append(req)
        self.queue[:] = still_queued
        for i, slot in enumerate(self.slots):
            if slot is not None and expired(slot.request):
                self.timeouts += 1
                self._retire(i, slot, "timeout")

    def _admit(self) -> None:
        now = self._clock()
        for i, s in enumerate(self.slots):
            if s is None and self.queue:
                if self._staged_swap is not None:
                    # A staged hot-swap pauses FRESH admissions (queued
                    # arrivals deterministically take the new weights), but
                    # a recompute-preempted stream is old-side work already
                    # in flight: it must resume on the old weights, both so
                    # its committed tokens never straddle the flip and so
                    # the drain the flip waits for can complete at all
                    # (sampling/ops.py).
                    qi = next(
                        (j for j, q in enumerate(self.queue)
                         if q.uid in self._resumed_uids),
                        None,
                    )
                else:
                    # Admission ORDER is the scheduler's call (FCFS: the
                    # queue head; SLO: earliest deadline first).
                    qi = self.scheduler.select_admit(self.queue, now)
                if qi is None:
                    break
                req = self.queue.pop(qi)
                # A preempted request restarts its k adaptation from
                # spec_k_max like a fresh one — the draft pool it re-prefills
                # is fresh too, so old acceptance evidence is stale anyway.
                slot = _Slot(req, self._admitted, [[] for _ in self.kinds], [0] * len(self.kinds),
                             spec_k=self.spec_k_max)
                if self.prefix_cache is not None:
                    # Map every fully-matched page into the slot's table and
                    # skip its prefill: the slot starts committed at the
                    # matched length and chunk-prefills only the tail. The
                    # len(prompt) - 1 cap guarantees the final prompt token
                    # is always re-prefilled, so first-token logits come
                    # from a live chunk (never from a skipped one).
                    with self._trace.span(
                        "trie.match", "prefix", self._obs_tid, req.uid
                    ):
                        mr = self.prefix_cache.match(
                            req.prompt, max_tokens=len(req.prompt) - 1
                        )
                    if mr.pages:
                        slot.pages[0] = list(mr.pages)
                        slot.n_shared = len(mr.pages)
                        slot.prompt_pos = slot.length = mr.tokens
                    ps = self.page_size
                    self._prefix_matchable_tokens += (
                        (len(req.prompt) - 1) // ps
                    ) * ps
                    self._prefix_matched_tokens += mr.tokens
                    if mr.cow_truncated:
                        self.cow_pages += 1
                    if self.spill_tier is not None:
                        self._readopt_from_spill(slot, req)
                self.slots[i] = slot
                self.pool.claim_state(slot, i)  # a family with a state kind: row i
                self._admitted += 1
                self._trace.instant(
                    "admitted", "lifecycle", self._obs_tid,
                    args={"uid": req.uid, "slot": i},
                )
                if self.obs is not None:
                    slot.skipped, slot.admit_round = slot.prompt_pos, self.rounds
                    self._req_phase(req.uid, "req.prefill", now)

    def _ensure_pages(self, slot: _Slot, upto_tokens: int) -> bool:
        """Grow slot's page list to cover positions [0, upto_tokens);
        True on success. On pool exhaustion, first reclaims unreferenced
        prefix-cache pages (LRU; a trie page nobody reads must never cost a
        live request a preemption), then asks the scheduler to pick a
        preemption victim among the STRICTLY YOUNGER running slots (the
        engine-enforced deadlock-freedom invariant: the oldest request
        always makes progress regardless of policy) and retries; False
        only when no younger victim exists or the policy defers."""
        want = -(-upto_tokens // self.page_size)
        for k, pages in enumerate(slot.pages):  # a plain loop: this runs for every slot every round
            if not self._grow(slot, k, want - len(pages)):
                return False
        return True

    def _grow(self, slot: _Slot, kind: int, need: int) -> bool:
        """`need` more logical pages of `kind` for `slot` (_ensure_pages)."""
        pool = self.pool
        while need > 0:
            got = pool.alloc(kind, need)
            if got is not None:
                slot.pages[kind].extend(got)
                pool.note_growth(slot, kind)
                return True
            if self.prefix_cache is not None:
                reclaimed = self.prefix_cache.evict(
                    need - pool.allocators[kind].free_count
                )
                if reclaimed:
                    pool.free(0, reclaimed)
                    self.prefix_evictions += len(reclaimed)
                    continue
            candidates = [
                s
                for s in self.slots
                if s is not None and s.admit_order > slot.admit_order
            ]
            if not candidates:
                return False
            victim = self.scheduler.select_victim(slot, candidates, self._clock())
            if victim is None:
                return False
            if not any(victim is c for c in candidates):
                raise RuntimeError(
                    f"scheduler {self.scheduler.name!r} returned a "
                    "non-candidate victim — preemption must pick from the "
                    "strictly-younger running slots it was offered"
                )
            self._evict(victim)
        return True

    def _evict(self, victim: _Slot) -> None:
        """Recompute-style preemption: fold generated tokens into the
        prompt, free the pages, and re-queue at the FRONT so the request
        resumes (by re-prefilling) as soon as the pool breathes.

        With the prefix cache on, "free" means release THROUGH the trie:
        the victim's complete committed pages become refcount-0 trie
        entries, and the folded prompt's first len - 1 tokens are exactly
        the committed content — so readmission re-matches every one of
        those pages and re-prefills only the sub-page tail plus the pending
        token, instead of the whole history (the r10 self-re-prefill fix,
        pinned by tests/test_prefix_cache.py). The released pages are also
        the freshest LRU entries, so pool pressure reclaims them last."""
        i = self.slots.index(victim)
        req = victim.request
        new_prompt = np.concatenate(
            [req.prompt, np.asarray(victim.generated, np.int32)]
        )
        self.queue.insert(
            0,
            Request(
                req.uid,
                new_prompt,
                req.max_new_tokens - len(victim.generated),
                req.eos_id,
                req.deadline,  # the clock keeps running across preemptions
                req.t_submit,
            ),
        )
        self._release_slot(victim)
        self.slots[i] = None
        self.preemptions += 1
        self._resumed_uids.add(req.uid)
        self._trace.instant(
            "preempt", "lifecycle", self._obs_tid, args={"uid": req.uid}
        )
        if self.obs is not None:
            self._req_phase(
                req.uid, "req.queue", self._clock(),
                end_args={"status": "preempted"}, begin_args={"resumed": True},
            )

    def _release_slot(self, slot: _Slot) -> None:
        """The ONE funnel a departing slot's pages go through (finish,
        cancel, timeout, preemption): `PagePool.release`, under a
        `trie.release` span where the prefix trie takes its share."""
        if self.prefix_cache is None:
            self.pool.release(slot)
            return
        with self._trace.span("trie.release", "prefix", self._obs_tid):
            self.pool.release(slot)

    def _sampling_key(self) -> tp.Optional[Array]:
        """The `key` argument of this engine's next program: the engine's
        key as the program before left it on the device (the program splits
        its own off it and hands back the next: the caller stores that as
        `_key`, unforced), or None, greedy: no key at all."""
        return None if self.temperature == 0.0 else self._key

    def _call_mark(self):
        """Counts one jit call of the serving path and hands back what it
        runs in: with obs on a `jax.profiler.TraceAnnotation`
        `engine.dispatch` carrying the call's number (about a microsecond of
        Python with no profiler running; inside a profile the annotation's
        start is the call's start on the PROFILER's clock, as the span that
        brackets the call is on the engine's: every dispatch is a sync
        mark), with obs off nothing. Opened here and not in
        midgpt_tpu/obs/, which imports no jax."""
        self.dispatches += 1
        if self.obs is None:
            return _NO_MARK
        return jax.profiler.TraceAnnotation("engine.dispatch", call=self.dispatches)

    def _count_blocks(
        self,
        lengths: np.ndarray,  # (B,) tokens cached per slot at dispatch
        active: np.ndarray,  # (B,) bool
        bucket: int,
        split_k: int,
        n_steps: int = 1,
        n_rows: int = 1,
    ) -> tp.Optional[tp.Tuple[int, int]]:
        """Record what the paged-attention kernel's grid does with this
        round: compute blocks swept and blocks live, per layer call, over
        the round's `n_steps` decode steps (step t's row r sees
        lengths + t + r + 1 keys; an inactive slot's rows see one,
        GPT.decode_step_paged). The block width is the kernel's own
        (`block_pages`, from the same shapes); the live rule is its
        `block_live` (`block_census`). Integers the round already holds:
        no device work. Kernel path only: the gather lowering has no blocks.
        Returns the two integers for the round's `decode.dispatch` span, or
        None where there are none or the family's `kernel_sweep` is one of
        its decode program's several kernels (`kernel_sweep_whole` False):
        the counters take them either way, a window's figure would not say
        which kernel it is of."""
        if self.obs is None or self.attn_impl != "kernel":
            return None
        (_, n_kv, _, ps, lanes), groups, window, sinks = self.model.kernel_sweep(
            self.config, self.cache
        )
        n_tp = 1 if self.mesh is None else int(self.mesh.shape["tp"])
        n = block_pages(
            n_kv // n_tp, lanes, self.cache_dtype.itemsize, ps,
            bucket // normalize_split_k(split_k, bucket),
            n_rows * groups,
        )
        steps = np.arange(1, n_steps + 1)[:, None]  # (n_steps, B) below
        first = np.maximum(active * (lengths + steps), 1).ravel()
        blocks = block_census(
            first, first + n_rows - 1, bucket, n, ps, window, sinks,
        )
        self.obs.record_decode_blocks(*blocks)
        return blocks if self.model.kernel_sweep_whole else None

    def _page_bucket(self, max_tokens: int) -> int:
        """Smallest power-of-two page count covering `max_tokens` positions
        (`PagePool.bucket`): a round's table is sliced to it."""
        return self.pool.bucket(max_tokens)

    def _split_bucket(self, max_tokens: int) -> int:
        """Static split-K factor for a round whose widest slot spans
        `max_tokens` positions: double the split for every page-bucket
        doubling past 512 tokens (so each partition sweeps >= 512 tokens),
        capped at 8. Traffic at or under 512 tokens resolves to 1 — the
        unsplit program, byte-identical to a split_k-naive engine — so the
        rule only engages (and only adds compile-cache entries) when long
        requests actually arrive. Forced int engines skip the rule; the
        kernels normalize the forced value to a pow2 divisor of the round's
        table width (kernels/attention_template.normalize_split_k)."""
        if self.split_k != "auto":
            return self.split_k
        tokens = self._page_bucket(max_tokens) * self.page_size
        split = 1
        while split < 8 and tokens // (2 * split) >= 512:
            split *= 2
        return split

    def _prefill_round(self) -> None:
        """Advance every mid-prompt slot by one (padded) chunk.

        One chunk per slot per round bounds how long any running decode
        stalls (a 30k prompt can't monopolize the device), while letting
        freshly admitted slots reach the decode batch in parallel — an
        empty decode slot is pure lost throughput. The chunks ride together
        as the rows of one program, `prefill_width` of them a call: the
        prefilling slots go in slot order in groups of that many, a group's
        pages found and its call made before the next group is looked at.
        `self.slots` is read as the walk reaches it: a slot that a group
        before evicted for its pages is gone by then. At width 1 this is a
        slot's pages, its call, the next slot."""
        group: tp.List[tp.Tuple[int, _Slot]] = []
        for slot_i in range(self.max_slots):
            slot = self.slots[slot_i]
            if slot is not None and slot.prefilling:
                group.append((slot_i, slot))
            if group and (len(group) == self.prefill_width or slot_i == self.max_slots - 1):
                self._prefill_group(group)
                group = []

    def _prefill_group(self, group: tp.List[tp.Tuple[int, _Slot]]) -> None:
        """Find the pages of every slot of `group`, then make ONE call over
        those that have them. The pages come BEFORE the batch is built:
        finding them may evict a younger slot, one of this group already
        given its pages included, and an evicted slot is in no batch."""
        rows: tp.List[tp.Tuple[int, _Slot, int]] = []
        for slot_i, slot in group:
            if self.slots[slot_i] is not slot:
                continue  # evicted for the pages of a row before it
            n_valid = min(self.prefill_chunk, len(slot.request.prompt) - slot.prompt_pos)
            # False: pool fully ours and still short — wait for finishes
            if self._ensure_pages(slot, slot.prompt_pos + n_valid):
                rows.append((slot_i, slot, n_valid))
        rows = [r for r in rows if self.slots[r[0]] is r[1]]
        if rows:
            self._prefill_call(rows)

    def _prefill_call(self, rows: tp.List[tp.Tuple[int, _Slot, int]]) -> None:
        """One prefill program over `rows` (slot index, slot, n_valid), at
        most `prefill_width` of them and each with its pages in hand; then
        the first token of every slot whose prompt that completed. The
        program samples it (`_serve_prefill_chunk`): the host pulls the
        call's `prefill_width` int32 tokens, once, and the logits they were
        sampled from only when `on_first_logits` asks for them."""
        # obs on: `prefill.assemble` runs from here to where `prefill.chunk`
        # opens (t_n: the chunk, starts and bucket are there; t_p: so are the
        # page tables' rows, sampled calls only: what follows is the host's
        # time on the key, two clock reads apart)
        obs = self.obs
        t0, c0 = (0.0, 0.0) if obs is None else (self._clock(), obs.cpu_clock())
        W = self.prefill_width
        chunk = np.zeros((W, self.prefill_chunk), np.int32)
        start, n_valid = np.zeros((W,), np.int32), np.zeros((W,), np.int32)
        for r, (_, slot, n) in enumerate(rows):
            pos = slot.prompt_pos
            chunk[r, :n] = slot.request.prompt[pos : pos + n]
            start[r], n_valid[r] = pos, n
        # the page bucket of a call is the bucket of its longest row
        bucket = self._page_bucket(int((start + n_valid).max()))
        t_n = 0.0 if obs is None else self._clock()
        table = self.pool.tables(self.slots, bucket, [slot_i for slot_i, _, _ in rows])
        # the one-row call every family takes: (numpy) scalars
        start_a, n_valid_a = (start, n_valid) if W > 1 else (start[0], n_valid[0])
        # one key a call, as a decode round has one: split off inside the program
        key = self._sampling_key()
        t_p = None if key is None or obs is None else self._clock()
        # The span covers the async ENQUEUE of ONE call, the transfer of its
        # numpy arguments with it, and nothing else: they were assembled
        # above (`prefill.assemble`), and nothing is forced here (a call
        # none of whose rows ends its prompt never syncs; the force happens
        # in the first-token block below). It belongs to no one request: rid
        # is the first row's. Its args say what rode the call and which
        # dispatch it is (`call`: the target's; a separate draft's call
        # below is the next number and has no span of its own).
        c_end = 0.0 if obs is None else obs.cpu_clock()
        call = self.dispatches + 1
        with self._trace.span(
            "prefill.chunk", "prefill", self._obs_tid, rows[0][1].request.uid,
            None if obs is None else
            {"rows": len(rows), "tokens": int(n_valid.sum()), "bucket": bucket,
             "call": call, "width": W},
        ) as sp:
            with self._call_mark():
                first, logits, self.cache, self._key = _serve_prefill_chunk(
                    self.config,
                    self.params,
                    chunk,
                    start_a,
                    n_valid_a,
                    self.cache,
                    table,
                    self.mesh,
                    self.attn_impl,
                    self.temperature,
                    self.top_k,
                    self.top_p,
                    key,
                )
            if self.draft_params is not None and not self.draft_shares_cache:
                # A separate draft model's pool must hold the same positions
                # as the target's — the spec round's draft steps attend
                # through the shared page table under the same per-slot
                # lengths. What the draft's program samples is discarded
                # (the pending token is the TARGET's). A prefix self-draft
                # skips this: the target prefill above already filled its
                # layers of the shared pool.
                with self._call_mark():
                    _, _, self.pool.draft_cache, _ = _serve_prefill_chunk(
                        self.draft_config,
                        self.draft_params,
                        chunk,
                        start_a,
                        n_valid_a,
                        self.pool.draft_cache,
                        table,
                        self.mesh,
                        self.attn_impl,
                    )
        if obs is not None:
            obs.record_prefill_assemble(
                self._obs_tid, rows[0][1].request.uid, t0, t_n, t_p, sp.t0,
                cpu_s=c_end - c0,
            )
        self.prefill_calls += 1
        pulled = False  # the call's tokens are on the host: pulled ONCE
        for r, (slot_i, slot, n) in enumerate(rows):
            slot.prompt_pos += n
            slot.length = slot.prompt_pos
            self.pool.reclaim(slot)  # long prompts free behind-window pages
            self.prefilled_tokens += n
            self.prefill_chunks += 1
            if slot.prefilling:
                continue
            if self.prefix_cache is not None:
                # The prompt's complete pages are immutable from here on
                # (every later write lands at a position >= len(prompt)):
                # share them so concurrent and future requests — including
                # this one after a preemption — skip their prefill.
                slot.n_shared = self.prefix_cache.insert_live(
                    slot.request.prompt, slot.pages[0], slot.n_shared
                )
            # Prompt complete: its first generated token is row r of what
            # the program sampled (greedy: the f32 argmax, as
            # engine.generate's sample_logits(temperature=0)). The
            # np.asarray is the call's one force/sync — the span of the
            # call's first finisher holds the device wait for the call plus
            # the pull of its W tokens (`call`: whose program's tokens these are).
            with self._trace.span(
                "prefill.first_token", "prefill", self._obs_tid,
                slot.request.uid, None if obs is None else {"call": call},
            ):
                if not pulled:
                    pulled = True
                    first = np.asarray(first)
                    if self.on_first_logits is not None:
                        logits = np.asarray(logits)
                        self.first_logit_pulls += 1
                if self.on_first_logits is not None:
                    self.on_first_logits(slot.request.uid, logits[r])
                tok = int(first[r])
            self.first_tokens += 1
            self._append_token(slot_i, slot, tok, self._clock())

    def _decode_budget(self) -> tp.Tuple[tp.List[int], int, str]:
        """(the slots a decode round would run: every slot past its prompt
        with tokens left; the steps none of them overshoots; which term set
        them: `"chunk"` (`decode_chunk`), `"remaining"` (the slot with the
        fewest tokens left) or `"block"` (the slot nearest `block_size`))."""
        active_idx = [
            i
            for i, s in enumerate(self.slots)
            if s is not None and not s.prefilling and s.remaining > 0
        ]
        if not active_idx:
            return [], 0, ""
        S = self.config.block_size
        remaining = min(self.slots[i].remaining for i in active_idx)
        budget = min(
            self.decode_chunk,
            remaining,
            min(S - self.slots[i].length for i in active_idx),
        )
        if budget == self.decode_chunk:
            return active_idx, budget, "chunk"
        return active_idx, budget, "remaining" if budget == remaining else "block"

    def _decode_pages(self, active_idx: tp.List[int], n: int) -> tp.List[int]:
        """Pages for `n` more steps of every slot in `active_idx`; the slots
        that got them (and were not evicted for another's)."""
        for i in list(active_idx):
            slot = self.slots[i]
            if slot is None:
                # An older slot's _ensure_pages earlier in this loop evicted
                # this one (eviction picks the youngest slot, which can sit
                # at any index). It is already re-queued; skip it.
                active_idx.remove(i)
                continue
            if not self._ensure_pages(slot, slot.length + n):
                # Reachable: the pool is held by slots at least as old as
                # this one, so there is no younger victim to evict. Defer
                # the slot to a later round; it resumes once older requests
                # finish and free pages.
                active_idx.remove(i)
        # A slot processed earlier in the loop can still be evicted by a
        # later, older slot's growth — drop any that went None.
        return [i for i in active_idx if self.slots[i] is not None]

    def _decode_args(self, active_idx: tp.List[int], n: int):
        """The host side of a decode dispatch over `active_idx` for `n` steps:
        (token, lengths, active) (max_slots,) arrays, and the positions the
        round's widest slot spans (what its page bucket and split-K factor
        follow)."""
        token = np.zeros((self.max_slots,), np.int32)
        lengths = np.zeros((self.max_slots,), np.int32)
        active = np.zeros((self.max_slots,), bool)
        for i in active_idx:
            s = self.slots[i]
            token[i] = s.generated[-1] if s.generated else s.request.prompt[-1]
            lengths[i] = s.length
            active[i] = True
        return token, lengths, active, max(self.slots[i].length for i in active_idx) + n

    def next_logits(self) -> tp.Dict[int, np.ndarray]:
        """uid -> float32 logits (V,) of the NEXT decode step of every slot
        the next decode round would run: one step of the family's
        `decode_step_paged` on that round's own arguments (this engine's
        cache, the page tables as the allocators and the window rule left
        them, the lengths, the page bucket and split-K factor), nothing
        sampled or committed. What `_serve_decode_chunk` samples its first
        token from; the step's K/V write is the one that round repeats. For
        checks (benchmarks/serve_family_cell.py, tests), not the serving
        loop: a program of its own a (page bucket, split), and a sync."""
        self._settle_inflight()
        active_idx, budget, _ = self._decode_budget()
        if not active_idx:
            return {}
        n = 1 << (budget.bit_length() - 1)  # as the round: the largest power of two <= budget
        active_idx = self._decode_pages(active_idx, n)
        if not active_idx:
            return {}
        token, lengths, active, round_span = self._decode_args(active_idx, n)
        with self._call_mark():
            logits, self.cache = _serve_decode_logits(
                self.config, self.params, token, self.cache,
                self.pool.tables(self.slots, self._page_bucket(round_span)), lengths,
                active, self.attn_impl, self.mesh, self._split_bucket(round_span),
            )
        logits = np.asarray(logits, np.float32)
        return {self.slots[i].request.uid: logits[i] for i in active_idx}

    def _decode_round(self) -> None:
        active_idx, budget, limit = self._decode_budget()
        if not active_idx:
            return
        n = 1 << (budget.bit_length() - 1)  # largest power of two <= budget
        active_idx = self._decode_pages(active_idx, n)
        if not active_idx:
            return

        # Round decomposition (obs/__init__.py docstring): t0 -> t1 is host
        # assembly + jit ENQUEUE (cut at t_a, t_k, t_p into assemble / key /
        # put / enqueue: the key is the device's own and costs the host two
        # clock reads, the put is the page tables' build, the enqueue is the
        # one call and carries the numpy arguments' transfer), t1 -> t_done
        # is device compute + the copy to the host (the np.asarray force is
        # the round's one sync), t_done -> t_post is token commit.
        obs = self.obs
        t0, c0 = (0.0, 0.0) if obs is None else (self._clock(), obs.cpu_clock())
        token, lengths, active, round_span = self._decode_args(active_idx, n)
        bucket = self._page_bucket(round_span)
        split_k = self._split_bucket(round_span)
        t_a = 0.0 if obs is None else self._clock()
        key = self._sampling_key()
        t_k = None if key is None or obs is None else self._clock()
        tables = self.pool.tables(self.slots, bucket)
        t_p = 0.0 if obs is None else self._clock()
        with self._call_mark():
            self.cache, toks, self._key = _serve_decode_chunk(
                self.config,
                self.params,
                token,
                self.cache,
                tables,
                lengths,
                active,
                n,
                self.temperature,
                self.top_k,
                self.top_p,
                self.attn_impl,
                key,
                self.mesh,
                split_k,
            )
        call = self.dispatches
        t1, c1 = (0.0, 0.0) if obs is None else (self._clock(), obs.cpu_clock())
        # counted after t1, while the device computes: not dispatch time
        blocks = self._count_blocks(lengths, active, bucket, split_k, n_steps=n)
        self.dispatch_log.append(
            (
                self.rounds,
                tuple(self.slots[i].request.uid for i in active_idx),
            )
        )
        # The round's ONE host<->device sync; watchdog-bounded when armed —
        # the force below is where a hung device would wedge forever.
        toks = self._force(
            lambda: np.asarray(toks), "serve.decode_sync"
        )  # (n, B)
        t_done = self._clock()
        if obs is not None:
            c_done = obs.cpu_clock()
            self._commit = [0, 0, 0.0]
        for i in active_idx:
            slot = self.slots[i]
            if slot is None:
                continue
            for j in range(n):
                slot.length += 1
                if self._append_token(i, slot, int(toks[j, i]), t_done):
                    break  # finished (max_new or EOS); rest of chunk discarded
        if obs is not None:
            obs.record_round(
                "decode", self._obs_tid, t0, t1, t_done, self._clock(),
                cuts=(t_a, t_k, t_p), steps=n, slots=len(active_idx),
                chunk=self.decode_chunk, limit=limit,
                tokens=self._commit[0], finished=self._commit[1],
                callback_s=self._commit[2],
                call=call, bucket=bucket, blocks=blocks,
                cpu=(c0, c1, c_done, obs.cpu_clock()),
            )

    def _spec_round(self) -> None:
        """One speculative round: k draft proposals per active slot (one
        program), one batched k+1-token verify forward + rejection sampler
        (one program), then host-side commit and page-aligned rollback.

        Rollback never touches device memory: a slot that accepted j of k
        drafts sets length = old + 1 + j and frees the tail pages past
        ceil(length / page_size) — the rejected columns stay in the pool,
        masked by every later read until the slot grows back over them
        (write-before-read; GPT.verify_step_paged docstring). k for the
        round is the pow2 min of the active slots' adaptive spec_k, so the
        compile set is one draft + one verify program per k bucket
        (tests/test_recompile_pins.py)."""
        active_idx = [
            i
            for i, s in enumerate(self.slots)
            if s is not None and not s.prefilling and s.remaining > 0
        ]
        if not active_idx:
            return
        S = self.config.block_size
        # submit() caps prompt + max_new at S, so an unfinished slot always
        # has length <= S - 2 and k_cap >= 1; the fallback is defensive
        # (a plain decode round also keeps the draft pool one round stale,
        # which only costs acceptance, never correctness).
        k_cap = min(S - 1 - self.slots[i].length for i in active_idx)
        budget = min([k_cap] + [self.slots[i].spec_k for i in active_idx])
        if budget < 1:
            self._decode_round()
            return
        k = 1 << (budget.bit_length() - 1)  # largest power of two <= budget
        active_idx = self._decode_pages(active_idx, k + 1)
        if not active_idx:
            return

        # Same four-boundary decomposition as _decode_round; t1 is taken
        # after the VERIFY call returns (both programs enqueued by then),
        # with draft/verify enqueue sub-spans recorded off the same reads.
        obs = self.obs
        t0, c0 = (0.0, 0.0) if obs is None else (self._clock(), obs.cpu_clock())
        token, lengths, active, round_span = self._decode_args(active_idx, k + 1)
        bucket = self._page_bucket(round_span)
        split_k = self._split_bucket(round_span)
        table = self.pool.table(self.slots, bucket)
        # drafts/draft_probs stay on device between the two dispatches —
        # the host only ever reads the small (B,) / (B, k+1) verify outputs.
        # With a prefix self-draft the draft steps run against the TARGET
        # pool (its first n_draft layers — ctor comment): the pool is
        # donated to the draft program and the returned one (speculative
        # columns written at the prefix layers) feeds verify, which
        # rewrites those columns with the identical values.
        shared = self.draft_shares_cache
        draft_cache_in = self.cache if shared else self.pool.draft_cache
        # The draft program makes the round's three-way key split: it hands
        # back the engine's next key and the verify program's, on the device.
        with self._call_mark():
            draft_cache_out, drafts, draft_probs, self._key, key_v = _spec_draft_chunk(
                self.draft_config,
                self.draft_params,
                token,
                draft_cache_in,
                table,
                lengths,
                active,
                k,
                self.temperature,
                self.top_k,
                self.top_p,
                self.attn_impl,
                self._sampling_key(),
                self.mesh,
                split_k,
            )
        t_draft = 0.0 if obs is None else self._clock()
        if shared:
            self.cache = draft_cache_out
        else:
            self.pool.draft_cache = draft_cache_out
        with self._call_mark():
            self.cache, n_accept, out = _spec_verify_chunk(
                self.config,
                self.params,
                token,
                drafts,
                draft_probs,
                self.cache,
                table,
                lengths,
                active,
                self.temperature,
                self.top_k,
                self.top_p,
                self.attn_impl,
                key_v,
                self.mesh,
                split_k,
            )
        call = self.dispatches  # the verify's; the draft's is the one before
        t1, c1 = (0.0, 0.0) if obs is None else (self._clock(), obs.cpu_clock())
        # the target's verify call (the draft's k steps run another model);
        # counted after t1, while the device computes: not dispatch time.
        # Its two integers go to the counters alone: the round's draft steps
        # are paged-attention calls of another geometry.
        self._count_blocks(lengths, active, bucket, split_k, n_rows=k + 1)
        n_accept = np.asarray(n_accept)
        out = np.asarray(out)  # forces both dispatches
        t_done = self._clock()
        c_done = 0.0 if obs is None else obs.cpu_clock()
        self._spec_rounds += 1
        for i in active_idx:
            slot = self.slots[i]
            if slot is None:
                continue
            j = int(n_accept[i])
            slot.length += 1 + j  # pending + accepted drafts are now cached
            self._spec_verifies += 1
            self._spec_drafted += k
            self._spec_accepted += j
            rate = j / k
            slot.accept_ema = 0.5 * slot.accept_ema + 0.5 * rate
            if self.spec_adapt:
                if slot.accept_ema > 0.75 and slot.spec_k * 2 <= self.spec_k_max:
                    slot.spec_k *= 2
                elif slot.accept_ema < 0.4 and slot.spec_k // 2 >= self.spec_k_min:
                    slot.spec_k //= 2
            finished = False
            for t in range(j + 1):
                if self._append_token(i, slot, int(out[i, t]), t_done):
                    finished = True  # EOS/budget; rest of the round discarded
                    break
            if finished:
                continue
            # page-aligned rollback: drop tail pages past the committed
            # length; the partial last page keeps its stale columns (masked).
            # In int8 mode the freed pages' scale entries are orphaned with
            # them — scales are indexed by physical page, so the same free
            # covers both, and both are rewritten before their page is next
            # read (write-before-read, GPT.verify_step_paged docstring).
            # Shared prefix pages sit below length (length >= matched + 1
            # from admission on), so keep > n_shared already; the max() is
            # a defensive floor — rollback must never hand a trie-owned
            # page to the allocator.
            keep = max(
                -(-slot.length // self.page_size), slot.n_shared
            )
            if len(slot.pages[0]) > keep:
                tail = slot.pages[0][keep:]
                del slot.pages[0][keep:]
                self.pool.free(0, tail)
        if obs is not None:
            obs.record_round(
                "spec", self._obs_tid, t0, t1, t_done, self._clock(),
                call=call, cpu=(c0, c1, c_done, obs.cpu_clock()),
            )
            self._trace.complete(
                "spec.draft_enqueue", "spec", self._obs_tid, t0, t_draft - t0,
                {"call": call - 1},
            )
            self._trace.complete(
                "spec.verify_enqueue", "spec", self._obs_tid, t_draft,
                t1 - t_draft, {"call": call},
            )

    def spec_stats(self) -> tp.Dict[str, float]:
        """Aggregate speculative counters since construction: acceptance
        rate (accepted drafts / drafted) and tokens emitted per verify
        forward per slot (1.0 would mean speculation never pays — every
        verify also yields its correction/bonus token)."""
        drafted = max(self._spec_drafted, 1)
        verifies = max(self._spec_verifies, 1)
        return {
            "rounds": self._spec_rounds,
            "accept_rate": self._spec_accepted / drafted,
            "tokens_per_verify": (self._spec_accepted + self._spec_verifies)
            / verifies,
        }

    def prefix_stats(self) -> tp.Dict[str, tp.Any]:
        """Prefix-cache counters since construction. `hit_rate` is matched / MATCHABLE prompt tokens, where matchable is
        the structural ceiling per admission — ((len(prompt) - 1) //
        page_size) * page_size, the most any match could hand out under the
        reserve-the-last-token rule — so a perfect template workload can
        actually reach 1.0. `prefilled_tokens` counts what went through
        prefill chunks; with sharing it is the complement of the hits (the
        r10 regression pin, tests/test_prefix_cache.py)."""
        pc = self.prefix_cache
        matchable = self._prefix_matchable_tokens
        return {
            "enabled": pc is not None,
            "matched_tokens": self._prefix_matched_tokens,
            "matchable_tokens": matchable,
            "hit_rate": (
                self._prefix_matched_tokens / matchable if matchable else 0.0
            ),
            "cow_pages": self.cow_pages,
            "prefilled_tokens": self.prefilled_tokens,
            "trie_pages": 0 if pc is None else pc.page_count(),
            "trie_referenced": 0 if pc is None else pc.referenced_page_count(),
            "reclaimed_pages": self.prefix_evictions,
        }

    def _finish(self, fr: FinishedRequest, t: tp.Optional[float] = None) -> None:
        """Record a terminal transition (ok/EOS/timeout/cancelled) and fire
        the streaming hook — the ONE funnel every path to `finished` goes
        through, so the async server never misses an ending. `t` is the
        last token's time where the caller has it (obs only)."""
        self.finished[fr.uid] = fr
        if self.obs is not None:
            self._commit[1] += 1
            self._req_phase(
                fr.uid, None, self._clock() if t is None else t,
                end_args={"tokens_out": len(fr.token_times), "status": fr.status},
            )
        self._trace.instant(
            "finish", "lifecycle", self._obs_tid,
            args={"uid": fr.uid, "status": fr.status},
        )
        if self.on_finish is not None:
            self.on_finish(fr)

    def _append_token(self, slot_i: int, slot: _Slot, tok: int, t: float) -> bool:
        """Record one generated token; returns True if the request finished
        (and the slot was freed)."""
        self.pool.reclaim(slot)  # no-op unless a kind has a window
        slot.generated.append(tok)
        slot.token_times.append(t)
        req = slot.request
        if self.obs is None:
            if self.on_token is not None:
                self.on_token(req.uid, tok, t)
        else:
            # the client's part of a commit, timed apart from the engine's
            commit = self._commit
            commit[0] += 1
            if self.on_token is not None:
                c0 = self._clock()
                self.on_token(req.uid, tok, t)
                commit[2] += self._clock() - c0
            if len(slot.generated) == 1:
                todo = len(req.prompt) - slot.skipped
                self._req_phase(
                    req.uid, "req.decode", t,
                    end_args={
                        "prompt_tokens": len(req.prompt),
                        "prefix_skipped": slot.skipped,
                        "chunks": -(-todo // self.prefill_chunk),
                        "rounds": self.rounds - slot.admit_round + 1,
                    },
                )
        hit_eos = req.eos_id is not None and tok == req.eos_id
        if hit_eos or len(slot.generated) >= req.max_new_tokens:
            self._retire(slot_i, slot, "ok", t)
            return True
        return False
