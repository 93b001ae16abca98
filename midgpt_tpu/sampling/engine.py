"""Autoregressive sampling with a static KV cache.

The reference's generate loop re-runs a full right-padded forward over the
whole block for EVERY new token (reference sample.py:68-95) — O(T) full
forwards. Here: one jitted prefill over the prompt, then one jitted
single-token decode step per new token against the (n_layer, B, H, S, C)
cache, with the cache buffers donated so XLA updates them in place. Both
functions have static shapes, so the loop compiles exactly twice.

If generation would run past `block_size`, decoding falls back to the
reference's windowed full-forward scheme for the overflow tokens (the cache
is sized to the trained context; RoPE positions past it are extrapolation).
"""

from __future__ import annotations

import functools
import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.models.gpt import GPT, GPTConfig, GPTParams, KVCache

Array = jax.Array


def warp_logits(
    logits: Array,  # (..., V) float32
    temperature: float,
    top_k: tp.Optional[int] = None,
    top_p: tp.Optional[float] = None,
) -> Array:
    """Temperature scaling + top-k / nucleus filtering on f32 logits.

    The warped logits DEFINE the sampling distribution: `sample_logits`
    draws categorically from them, and the speculative-decoding rejection
    sampler (sampling/spec.py) needs the same warped distribution for both
    the draft and the target, so the filter lives here as a pure function.
    Requires temperature > 0 (greedy has no distribution to warp); works on
    any leading batch shape."""
    logits = logits / temperature
    if top_k is not None and top_k < logits.shape[-1]:
        # lax.top_k is O(V) selection of k values — not a full-vocab sort
        # per token (the nucleus path below can't avoid its sort).
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        # nucleus: keep the smallest prefix of descending-prob tokens whose
        # cumulative mass reaches top_p (the first token is always kept —
        # its exclusive prefix mass is 0)
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
        keep = exclusive_cum < top_p
        threshold = jnp.min(
            jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < threshold, -jnp.inf, logits)
    return logits


def sample_logits(
    logits: Array,  # (B, V) float
    key: Array,
    temperature: float = 1.0,
    top_k: tp.Optional[int] = None,
    top_p: tp.Optional[float] = None,
) -> Array:
    """Temperature + optional top-k / nucleus (top-p) sampling; 0 = greedy."""
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(
        key, warp_logits(logits, temperature, top_k, top_p), axis=-1
    )


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6))
def _prefill_and_first(config, params, tokens, key, temperature, top_k, top_p):
    logits, cache = GPT.prefill(config, params, tokens, KVCache.init(
        config, tokens.shape[0], dtype=tokens_dtype(params)))
    first = sample_logits(logits[:, -1], key, temperature, top_k, top_p)
    return first, cache


def tokens_dtype(params: GPTParams):
    return params.wte.dtype


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6), donate_argnums=(3,))
def _decode_and_sample(config, params, token, cache, temperature, top_k, top_p, key):
    logits, cache = GPT.decode_step(config, params, token, cache)
    nxt = sample_logits(logits, key, temperature, top_k, top_p)
    return nxt, cache


# Tokens decoded per device dispatch. Each host->device round trip costs
# ~5-8 ms under remote-TPU setups (far more than a 124M decode step), so the
# per-token python loop is latency-bound; a lax.scan of decode steps inside
# one jit amortizes the dispatch over the whole chunk.
DECODE_CHUNK = 64
assert DECODE_CHUNK & (DECODE_CHUNK - 1) == 0, "tail decomposition assumes a power of two"


@functools.partial(jax.jit, static_argnums=(0,))
def _window_forward(config, params, window):
    """Full forward on a static (B, S) window -> last-position logits.

    Module-level jit (NOT a fresh jax.jit per generate call): the overflow
    window is always exactly block_size wide — the fast path only exits the
    cache once T_ctx + produced > S, so seq is at least S+1 long by the
    first overflow token — giving ONE compile per (B, S) across all calls."""
    return GPT.apply(config, params, window, inference=True)[:, -1]


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6, 7), donate_argnums=(3,))
def _decode_chunk(config, params, token, cache, temperature, top_k, top_p, n_steps, key):
    """n_steps sequential decode+sample steps as ONE device program.

    Returns (last_token, cache, tokens (n_steps, B))."""

    def body(carry, _):
        token, cache, key = carry
        key, k = jax.random.split(key)
        logits, cache = GPT.decode_step(config, params, token, cache)
        nxt = sample_logits(logits, k, temperature, top_k, top_p)
        return (nxt, cache, key), nxt

    (token, cache, _), toks = jax.lax.scan(
        body, (token, cache, key), None, length=n_steps
    )
    return token, cache, toks


def restore_for_sampling(
    ckpt_dir: str,
    config,  # ExperimentConfig (duck-typed to avoid an import cycle)
    mesh=None,
) -> tp.Tuple[GPTParams, int]:
    """Restore the 'params' item sharded over an inference mesh.

    The naive restore targets ONE device — a 7B checkpoint can never load
    that way. Here the abstract skeleton carries NamedShardings from the
    same FSDP spec rule training uses, so Orbax reads each host's shards
    straight into sharded device arrays (training/checkpoint.py restore
    honors the target shardings), and the decode jits inherit the layout
    via GSPMD. With one device (or mesh=None on a 1-chip host) this reduces
    to the plain single-device restore. Returns (params, step)."""
    from midgpt_tpu.parallel.fsdp import fsdp_param_specs, named_shardings
    from midgpt_tpu.training.checkpoint import CheckpointManager

    if mesh is None:
        from midgpt_tpu.config import MeshConfig
        from midgpt_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(MeshConfig(data=1, fsdp=jax.device_count(), sp=1))
    model_cfg = config.model_config
    abstract = jax.eval_shape(
        lambda k: model_cfg.model().init(model_cfg, k), jax.random.PRNGKey(0)
    )
    specs = fsdp_param_specs(
        abstract,
        mesh,
        shard_model=mesh.shape["fsdp"] > 1,
        min_size=config.fsdp_min_size,
    )
    shardings = named_shardings(specs, mesh)
    abstract = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, jnp.dtype(config.param_dtype), sharding=sh
        ),
        abstract,
        shardings,
    )
    mngr = CheckpointManager(ckpt_dir)
    # Verified steps only (training/checkpoint.py manifests): never sample
    # from a save truncated by a mid-save kill. Pre-manifest checkpoint
    # dirs fall back to the plain latest step.
    step = mngr.latest_verified_step()
    if step is None:
        raise FileNotFoundError(f"no verified checkpoint found under {ckpt_dir}")
    params = mngr.restore(step, {"params": abstract})["params"]
    return params, step


def check_batch_engine(config) -> None:
    """`generate` runs the GPT's dense KV cache (`GPT.prefill` /
    `GPT.decode_step`) and no other family's: refused by name, never switched."""
    if not isinstance(config, GPTConfig):
        raise NotImplementedError(
            f"the batch engine (sampling/engine.py generate) holds the GPT's dense KV cache only; "
            f"family {config.family!r} is served by ServeEngine: sample.py --engine=continuous"
        )


def generate(
    config: GPTConfig,
    params: GPTParams,
    prompt: Array,  # (B, T0) int32
    max_new_tokens: int,
    *,
    temperature: float = 1.0,
    top_k: tp.Optional[int] = None,
    top_p: tp.Optional[float] = None,
    key: tp.Optional[Array] = None,
) -> Array:
    """Returns (B, T0 + max_new_tokens) including the prompt."""
    check_batch_engine(config)
    key = key if key is not None else jax.random.PRNGKey(0)
    B, T0 = prompt.shape
    S = config.block_size
    prompt = jnp.asarray(prompt, jnp.int32)
    if T0 > S:
        prompt_ctx = prompt[:, -S:]
    else:
        prompt_ctx = prompt

    out = [prompt]
    key, k0 = jax.random.split(key)
    nxt, cache = _prefill_and_first(
        config, params, prompt_ctx, k0, temperature, top_k, top_p  # graftcheck: disable=GC011 — one-shot CLI sampler: config and sampling knobs come from argparse and are process-constant; one compile per process is the contract (ServeEngine pins them init-frozen instead)
    )
    out.append(nxt[:, None])
    produced = 1

    # Fast path: incremental decode while the write position fits the cache.
    # Decode call #i writes K/V at position T_ctx + i; a chunk of n steps
    # starting at call index (produced - 1) last writes T_ctx + produced +
    # n - 2, which must stay <= S - 1. Chunks run as one device program
    # (DECODE_CHUNK tokens per dispatch); a partial tail is decomposed into
    # power-of-two chunks, so the scan only ever compiles at lengths
    # {DECODE_CHUNK, DECODE_CHUNK/2, ..., 1} — a bounded, request-pattern-
    # independent compile set (at most log2(DECODE_CHUNK) extra dispatches
    # per generation).
    T_ctx = int(min(T0, S))
    while produced < max_new_tokens and T_ctx + produced <= S:
        budget = min(
            DECODE_CHUNK,
            max_new_tokens - produced,
            S - T_ctx - produced + 1,
        )
        n = 1 << (budget.bit_length() - 1)  # largest power of two <= budget
        key, k = jax.random.split(key)
        nxt, cache, toks = _decode_chunk(
            config, params, nxt, cache, temperature, top_k, top_p, n, k  # graftcheck: disable=GC011 — one-shot CLI sampler: knobs are process-constant argparse values (n itself is pow2-clamped)
        )
        out.append(toks.T)  # (B, n)
        produced += n

    # Overflow: windowed full-forward per token (reference scheme). The
    # window is a static (B, S) slice — see _window_forward.
    if produced < max_new_tokens:
        seq = jnp.concatenate(out, axis=1)
        for _ in range(max_new_tokens - produced):
            key, k = jax.random.split(key)
            window = seq[:, -S:]
            nxt = sample_logits(
                _window_forward(config, params, window), k, temperature, top_k, top_p  # graftcheck: disable=GC011 — one-shot CLI sampler: config is process-constant; the overflow window compiles once
            )
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        return seq

    return jnp.concatenate(out, axis=1)
