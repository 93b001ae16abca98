"""Ouro: a LOOPED transformer. One stack of `n_layer` layers is applied `n_loop`
times with the SAME parameters; the final norm and an exit gate follow every
pass, and each pass keeps keys and values of its own, so the paged cache
(models/gpt.py `ServeCache`: `pools` = ((K, V),), the GPT pool's layout) has
`n_loop * n_layer` layers for `n_layer` layers of weights. SERVED (sample.py,
ServeEngine); training is refused by name (`check_training`).

Source: https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
(`model_type: ouro`: 48 layers, hidden 2,048, 16 heads = 16 K/V heads of 128,
SwiGLU 5,632, rms_norm_eps 1e-6, rope_theta 1e6, vocabulary 49,152, untied
head, `total_ut_steps` 4, `early_exit_threshold` 1; arXiv:2510.25741).

With n(x; g) = g * x / sqrt(mean(x^2) + eps), all four norms of a layer weighted:

    h = E[tokens]
    for r in 1..n_loop:                          # the same layers each pass
        x = h
        for l in 1..n_layer:
            q, k, v = heads(n(x; g1) W_q^T), heads(n(x; g1) W_k^T), heads(n(x; g1) W_v^T)
            q, k = rope(q), rope(k)              # rotate-half, every channel
            o = causal softmax(q K[r,l]^T / sqrt(C)) V[r,l]   # cache layer (r-1) * n_layer + (l-1)
            x = x + n(o W_o^T; g2)               # sandwich: the sublayer's OUTPUT is normed
            x = x + n(swiglu(n(x; g3)); g4)
        h = n(x; g_f)                            # pass r + 1 starts from the NORMED state
        lambda_r = sigmoid(w_e . h + b_e)        # exit gate
    logits = h W_head^T

The exit distribution is p_r = lambda_r prod_{j<r} (1 - lambda_j) for r < n_loop
and the mass that is left for the last pass. At the published threshold of 1 no
pass before the last reaches it: `n_loop` passes always run and the logits are
the last pass's; `p` is computed and COUNTED (`serve_counters`). An exit that
stops early, and the variants that keep fewer cache layers, are not wired.

The parameters are STACKED over layers and every forward is ONE rolled loop
over passes around ONE rolled loop over layers (`_run`), so a program's size
and its tracing time depend on neither `n_layer` nor `n_loop`. On the paged
path the pools ride both loops' carries; the write and the attention address
pool row `r * n_layer + l` by a traced index (`_paged_write`, `paged_attention`
take `layer=`), and nothing slices a layer out (PagedKVCache "Layout contract").
"""

from __future__ import annotations

import dataclasses
import math
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np

from midgpt_tpu.models.gpt import GPT, CacheKind, ServeCache, _gather_layer_kv, _paged_write
from midgpt_tpu.ops.moe import swiglu
from midgpt_tpu.ops.norms import rms_norm
from midgpt_tpu.ops.rope import apply_rope_leading, rope_table
from midgpt_tpu.utils.pytree import pytree_dataclass

Array = jax.Array
KeyArray = jax.Array

FAMILY = "ouro"
LOOPED = "looped"


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """Model shape, by the published keys' meaning. The first five fields are
    named as `GPTConfig` names them (models/__init__.py)."""

    block_size: int  # serving cap on prompt + output (max_position_embeddings 65,536)
    vocab_size: int
    n_layer: int  # num_hidden_layers: layers of WEIGHTS; the cache has n_loop times as many
    n_head: int  # num_attention_heads = num_key_value_heads
    n_embd: int  # hidden_size
    n_loop: int = 4  # total_ut_steps: times the stack is applied
    head_dim: int = 128
    dense_width: int = 5632  # intermediate_size
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    early_exit_threshold: float = 1.0  # published; below 1 a pass could be the last: not wired
    family: str = FAMILY  # discriminates model_config in config.json

    def __post_init__(self):
        if self.family != FAMILY:
            raise ValueError(f"family={self.family!r} is not {FAMILY!r}")
        if self.n_loop < 1:
            raise ValueError(f"n_loop={self.n_loop} must be at least 1")
        if self.head_dim % 2:
            raise ValueError(f"head_dim={self.head_dim}: rotate-half needs an even width")
        if self.early_exit_threshold < 1.0:
            raise ValueError(
                f"early_exit_threshold={self.early_exit_threshold}: an exit before the last pass is not wired "
                "(every slot runs n_loop passes a step)"
            )

    # -- what the runtime reads of any model config (models/__init__.py) --
    def model(self):
        return Ouro

    def check_experiment(self, config) -> None:
        m = config.mesh
        over = {a: getattr(m, a) for a in ("fsdp", "sp", "tp", "pp", "ep") if getattr(m, a) not in (1, -1)}
        if over or config.shard_model:
            raise ValueError(f"{FAMILY}: no mesh axis is wired (got {over or 'shard_model=True'})")
        if config.spec_layers:
            raise ValueError(f"{FAMILY}: spec_layers needs a verify step over the looped cache, which is not wired")

    def check_training(self, who: str) -> None:
        raise NotImplementedError(
            f"{who} cannot train a {FAMILY} model: its loss is the exit-weighted sum of the passes' cross-entropies "
            "with an entropy term over the exit distribution, and neither that loss nor a backward through the looped "
            "stack is wired. Serve it: sample.py --engine=continuous, ServeEngine."
        )

    def check_serving(self, who: str) -> None:
        """sample.py's continuous engine and ServeEngine serve this family."""

    @property
    def cache_layers(self) -> int:
        """Layers of the paged cache: every pass of every layer keeps its own keys and values."""
        return self.n_loop * self.n_layer


@pytree_dataclass
class LayerParams:
    """One layer's parameters; in `OuroParams.layers` every leaf is STACKED
    over layers (leading dim n_layer)."""

    norm_in: Array  # (D,) input_layernorm
    wq: Array  # (H * C, D)
    wk: Array  # (H * C, D)
    wv: Array  # (H * C, D)
    wo: Array  # (D, H * C)
    norm_post_attn: Array  # (D,) input_layernorm_2, on attention's output
    norm_pre_mlp: Array  # (D,) post_attention_layernorm
    w_gate: Array  # (F, D)
    w_up: Array  # (F, D)
    w_down: Array  # (D, F)
    norm_post_mlp: Array  # (D,) post_attention_layernorm_2, on the MLP's output


@pytree_dataclass
class OuroParams:
    wte: Array  # (V, D)
    layers: LayerParams  # leaves (n_layer, ...)
    final_norm: Array  # (D,), applied after EVERY pass
    exit_w: Array  # (D,) the exit gate, Linear(D, 1)
    exit_b: Array  # ()
    lm_head: Array  # (V, D), untied


# What `init` seeds the two SANDWICH norms' gains at (the norms on a sublayer's output; every other gain: 1). A looped
# model applies one map four times, and a trained one is usable only if that map does not blow a perturbation up from
# pass to pass. Seeded at gain 1 it does: every sublayer adds a unit-RMS vector, and the error of a bf16 forward against
# the float32 one grows 2.7e-2 -> 6.4e-2 -> 3.4e-1 of the logits' std over 1, 2, 4 passes of 48 layers (CPU, width 256;
# on the chip at the published widths 2.3e-1 to 2.6e-1 over five seeds), which would say more about the seed than about
# the program. At 0.1 a pass still changes the state by about its own size (96 sums of 0.1: ~1) and the same readings
# are 2.5e-2 -> 2.8e-2 -> 3.3e-2 (PERF.md section 6 PR 41).
POST_NORM_INIT = 0.1

_F32_LEAVES = ("norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp", "final_norm", "exit_w", "exit_b")


def _linear(key: KeyArray, out_features: int, in_features: int) -> Array:
    return jax.random.truncated_normal(key, -2.0, 2.0, (out_features, in_features)) / math.sqrt(in_features)


def _norm(c: OuroConfig, x: Array, w: Array, dtype=None) -> Array:
    """Weighted RMSNorm in float32, handed on in `dtype` (x's own where not given)."""
    return rms_norm(x.astype(jnp.float32), w.astype(jnp.float32), c.rms_norm_eps).astype(dtype or x.dtype)


def _embed(params: "OuroParams", tokens: Array) -> Array:
    """Token rows of the embedding as the float32 residual stream (`Ouro._run`)."""
    with jax.named_scope("embed"):
        return jnp.take(params.wte, tokens, axis=0).astype(jnp.float32)


class Ouro:
    """Namespace of pure functions over (OuroConfig, OuroParams)."""

    weight_decay_mask = None
    route_stats = None
    verify_step_paged = None  # no speculative verify over the looped cache
    # a prefill call reads the layers' weights n_loop times whatever rides it: the round's slots ride as rows
    prefill_batched = True

    prefill_rows = staticmethod(GPT.prefill_rows)  # every weight is dense and sees every row: the ridge's rows

    @staticmethod
    def init(config: OuroConfig, key: KeyArray) -> OuroParams:
        c = config
        D, E, F = c.n_embd, c.n_head * c.head_dim, c.dense_width

        def init_layer(k: KeyArray) -> LayerParams:
            ks = jax.random.split(k, 7)
            ones, post = jnp.ones((D,)), jnp.full((D,), POST_NORM_INIT)
            return LayerParams(
                norm_in=ones, wq=_linear(ks[0], E, D), wk=_linear(ks[1], E, D), wv=_linear(ks[2], E, D),
                wo=_linear(ks[3], D, E), norm_post_attn=post, norm_pre_mlp=ones,
                w_gate=_linear(ks[4], F, D), w_up=_linear(ks[5], F, D), w_down=_linear(ks[6], D, F),
                norm_post_mlp=post,
            )

        k_embed, k_head, k_gate, k_layers = jax.random.split(key, 4)
        return OuroParams(
            wte=jax.random.normal(k_embed, (c.vocab_size, D)) / math.sqrt(D),
            layers=jax.vmap(init_layer)(jax.random.split(k_layers, c.n_layer)),
            final_norm=jnp.ones((D,)), exit_w=_linear(k_gate, 1, D)[0], exit_b=jnp.zeros(()),
            lm_head=jax.random.normal(k_head, (c.vocab_size, D)) / math.sqrt(D),
        )

    @staticmethod
    def cast_params(params: OuroParams, dtype) -> OuroParams:
        """The compute copy: matrices in `dtype`; norm gains and the exit gate as they are."""
        return jax.tree_util.tree_map_with_path(
            lambda path, p: p if str(getattr(path[-1], "name", path[-1])) in _F32_LEAVES
            or not jnp.issubdtype(p.dtype, jnp.floating) else p.astype(dtype),
            params,
        )

    @staticmethod
    def count_params(params: OuroParams) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    @staticmethod
    def param_specs(config, tree, mesh):
        del config, mesh  # every leaf replicated: no mesh axis is wired (check_experiment)
        return jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree)

    @staticmethod
    def flops_per_token(config: OuroConfig, seq_len: tp.Optional[int] = None, stats=None) -> float:
        """FORWARD FLOPs a token (this family is served, not trained): 2 x the
        parameters a token multiplies, the layers' n_loop times, plus scores
        and values over a causal context in every pass of every layer."""
        del stats
        c = config
        T = seq_len or c.block_size
        E = c.n_head * c.head_dim
        layer = 4 * c.n_embd * E + 3 * c.n_embd * c.dense_width + 2 * E * T / 2
        return 2.0 * (c.n_loop * (c.n_layer * layer + c.n_embd) + c.vocab_size * c.n_embd)

    # ------------------------------------------------------------------
    # the looped stack: what every forward shares
    # ------------------------------------------------------------------

    @staticmethod
    def _run(c: OuroConfig, params: OuroParams, x: Array, positions: Array, state, attend):
        """The stack applied `n_loop` times to x (B, T, D) at `positions` ((T,)
        or (B, T)). x is FLOAT32 and stays so through both loops (the residual
        stream: 12 or 256 rows of n_embd, nothing beside the weight reads; kept
        in bf16 its rounding adds up over n_loop * n_layer * 2 sums: the bf16
        forward's error read 3.3e-2 of the logits' std against 2.2e-2, CPU,
        width 256); what a matrix multiplies is cast to the matrix's dtype. `attend(state, row, q, k, v) -> (o, state)` is the caller's
        attention over (B, T, H, C) for cache row `row` = r * n_layer + l (a
        traced scalar); `state` rides both loops' carries (the paged pools, or
        None). Returns (the last pass's normed state (B, T, D), the exit
        distribution p (n_loop, B, T) float32, state)."""
        B, T, _ = x.shape
        rope = rope_table(c.head_dim, c.block_size, c.rope_theta)

        def layer(r, carry, l):
            x, state = carry
            # layer l's slice of a stacked leaf, taken INSIDE the scope that uses it: the slice of a matrix is
            # its read (the compiler fuses it into the matmul or prefetches it), and belongs to that scope's time
            own = lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False)
            p = params.layers
            with jax.named_scope("attn"):
                a = _norm(c, x, own(p.norm_in), p.wq.dtype)
                heads = lambda w: jnp.einsum("btd,ed->bte", a, own(w)).reshape(B, T, c.n_head, c.head_dim)
                q = apply_rope_leading(heads(p.wq), *rope, positions)
                k = apply_rope_leading(heads(p.wk), *rope, positions)
                o, state = attend(state, r * c.n_layer + l, q, k, heads(p.wv))
                o = jnp.einsum("bte,de->btd", o.astype(p.wo.dtype).reshape(B, T, -1), own(p.wo))
                x = x + _norm(c, o, own(p.norm_post_attn), x.dtype)
            with jax.named_scope("mlp"):
                y = swiglu(_norm(c, x, own(p.norm_pre_mlp), p.w_gate.dtype), own(p.w_gate), own(p.w_up), own(p.w_down))
                x = x + _norm(c, y, own(p.norm_post_mlp), x.dtype)
            return (x, state), None

        def one_pass(r, carry):
            x, state, left, p = carry
            with jax.named_scope("loop"):
                (x, state), _ = jax.lax.scan(
                    lambda carry, l: layer(r, carry, l), (x, state), jnp.arange(c.n_layer, dtype=jnp.int32)
                )
                h = _norm(c, x, params.final_norm)
                with jax.named_scope("exit_gate"):
                    lam = jax.nn.sigmoid(jnp.einsum("btd,d->bt", h.astype(jnp.float32), params.exit_w) + params.exit_b)
                    # the last pass takes the mass that is left: p sums to 1
                    p = jax.lax.dynamic_update_index_in_dim(p, jnp.where(r == c.n_loop - 1, left, lam * left), r, 0)
                    left = left * (1.0 - lam)
            return h, state, left, p

        init = (x, state, jnp.ones((B, T), jnp.float32), jnp.zeros((c.n_loop, B, T), jnp.float32))
        h, state, _, p = jax.lax.fori_loop(0, c.n_loop, one_pass, init)
        return h, p, state

    @staticmethod
    def _head(params: OuroParams, h: Array) -> Array:
        with jax.named_scope("lm_head"):
            return jnp.einsum("btd,vd->btv", h.astype(params.lm_head.dtype), params.lm_head)

    # ------------------------------------------------------------------
    # the plain full forward (tests; no cache)
    # ------------------------------------------------------------------

    @staticmethod
    def forward(config: OuroConfig, params: OuroParams, tokens: Array) -> tp.Tuple[Array, Array]:
        """Whole sequences (B, T) under an explicit causal mask -> (the last
        pass's normed hidden states (B, T, D), the exit distribution (B, T, n_loop))."""
        c = config
        pos = jnp.arange(tokens.shape[1])
        keep = pos[None, :] <= pos[:, None]

        def attend(state, row, q, k, v):
            s = jnp.einsum("bthc,bshc->bhts", q, k).astype(jnp.float32) / math.sqrt(c.head_dim)
            prob = jax.nn.softmax(jnp.where(keep, s, float("-inf")), axis=-1).astype(v.dtype)
            return jnp.einsum("bhts,bshc->bthc", prob, v), state

        h, p, _ = Ouro._run(c, params, _embed(params, tokens), pos, None, attend)
        return h, jnp.moveaxis(p, 0, -1)

    @staticmethod
    def hidden(config: OuroConfig, params: OuroParams, tokens: Array, *, key=None,
               inference: bool = False, attn_fn=None) -> Array:
        del key, inference
        if attn_fn is not None:
            raise ValueError(f"{FAMILY}: a mesh-bound attn_fn is not wired")
        return Ouro.forward(config, params, tokens)[0]

    @staticmethod
    def apply(config: OuroConfig, params: OuroParams, tokens: Array) -> Array:
        """Logits (B, T, V) of whole sequences."""
        return Ouro._head(params, Ouro.hidden(config, params, tokens))

    # ------------------------------------------------------------------
    # serving (sampling/serve.py reaches these through models/__init__.py)
    # ------------------------------------------------------------------

    @staticmethod
    def cache_kinds(config: OuroConfig) -> tp.Tuple[CacheKind, ...]:
        """One kind: every pass of every layer keeps the whole context."""
        return (CacheKind(LOOPED, 0, 0),)

    @staticmethod
    def init_cache(config: OuroConfig, num_pages: tp.Sequence[int], page_size: int = 8,
                   dtype=jnp.bfloat16, kernel_layout: bool = False) -> ServeCache:
        """Zeroed K and V pools of `num_pages[0]` pages and `n_loop * n_layer`
        cache layers (row `r * n_layer + l`: pass r of layer l). Counters, of
        decoded tokens: `loop_steps` (2,) int32 (decode steps, passes run over
        active slots) and `exit_mass` (n_loop,) float32 (sums of the exit
        distribution)."""
        c = config
        return ServeCache.zeros(FAMILY, (((c.cache_layers, c.n_head, c.head_dim),) * 2,), num_pages, page_size, dtype, kernel_layout,
                                (jnp.zeros((2,), jnp.int32), jnp.zeros((c.n_loop,), jnp.float32)))

    kernel_sweep_whole = True  # every pass of every layer is this one kernel call

    @staticmethod
    def kernel_sweep(config: OuroConfig, cache: ServeCache):
        """(pool shape, q rows a pool head, window, sinks) of the decode
        kernel's sweep, for the engine's block counters; the pool's layer dim
        says how many sweeps a step makes."""
        return cache.pools[0][0].shape, 1, 0, 0

    @staticmethod
    def serve_counters(config: OuroConfig, cache: ServeCache) -> tp.Dict[str, float]:
        """`loop.decode_steps`; `loop.passes_run` (n_loop x steps x active slots:
        what an exit that stops early would lower); `loop.exit_mass_<r>`, the
        exit distribution summed over decoded tokens, and its mean pass
        `loop.exit_pass_expected`; and what the pools keep of a token over all
        n_loop * n_layer cache layers, in bytes."""
        steps, mass = jax.device_get(cache.counters)
        mass = np.asarray(mass, np.float64)
        out = {"loop.decode_steps": int(steps[0]), "loop.passes_run": int(steps[1]),
               f"kv.{LOOPED}_bytes_per_token": sum(a.nbytes for a in cache.pool_arrays()) / (cache.num_pages * cache.page_size)}
        out.update({f"loop.exit_mass_{r + 1}": float(m) for r, m in enumerate(mass)})
        if mass.sum() > 0:
            out["loop.exit_pass_expected"] = float(np.dot(mass, np.arange(1, len(mass) + 1)) / mass.sum())
        return out

    @staticmethod
    def decode_step_paged(config: OuroConfig, params: OuroParams, token: Array, cache: ServeCache,
                          page_table: Array, lengths: Array, active: Array,
                          attn_impl: str = "auto", mesh=None, split_k: int = 1) -> tp.Tuple[Array, ServeCache]:
        """One decode step for B requests at B positions (GPT.decode_step_paged's
        contract). Slot b writes its token's K/V at position lengths[b] in all
        n_loop * n_layer cache layers, pass r of layer l attending to lengths[b]
        + 1 keys of row r * n_layer + l alone. Inactive slots write nothing and
        read one masked-in garbage key. Returns (logits (B, V), cache)."""
        from midgpt_tpu.kernels.decode_attention import paged_attention, resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        ps, pos = cache.page_size, lengths
        counts = jnp.maximum(active.astype(jnp.int32) * (pos + 1), 1)  # (B,)
        write_pages = jnp.where(active, jnp.take_along_axis(page_table, (pos // ps)[:, None], axis=1)[:, 0], cache.num_pages)
        offs = pos % ps

        def attend(pools, row, q, k, v):
            ck, cv, _, _ = _paged_write((*pools, None, None), row, write_pages, offs, k[:, 0], v[:, 0], attn_impl, None)
            o = paged_attention(q[:, 0], ck, cv, page_table, counts, impl=attn_impl, split_k=split_k, layer=row)
            return o[:, None], (ck, cv)

        h, p, (ck, cv) = Ouro._run(c, params, _embed(params, token[:, None]), pos[:, None], cache.pools[0], attend)
        n_active = jnp.sum(active.astype(jnp.int32))
        loop_steps, exit_mass = cache.counters
        steps = loop_steps + jnp.stack([jnp.ones((), jnp.int32), c.n_loop * n_active])
        mass = exit_mass + jnp.sum(jnp.where(active[None, :], p[:, :, 0], 0.0), axis=1)
        return Ouro._head(params, h)[:, 0], ServeCache(pools=((ck, cv),), counters=(steps, mass))

    @staticmethod
    def prefill_paged_chunk(config: OuroConfig, params: OuroParams, tokens: Array, start: Array, n_valid: Array,
                            cache: ServeCache, page_table: Array,
                            attn_impl: str = "auto", mesh=None) -> tp.Tuple[Array, ServeCache]:
        """The prompt chunks of B requests, row b's being [start[b], start[b] +
        n_valid[b]), into their pages in all n_loop * n_layer cache layers
        (GPT.prefill_paged_chunk's contract: written first, then each row
        attends to its slot's gathered pages under its own length mask; XLA on
        every backend, `attn_impl` chooses the WRITE). Returns (logits of each
        row's last valid position (B, V), cache); the ONE-ROW call (scalar
        `start` / `n_valid`) returns (1, 1, V). The exit gate's distribution is
        not counted here: the counters are of decoded tokens."""
        from midgpt_tpu.kernels.decode_attention import resolve_paged_impl

        if mesh is not None:
            raise NotImplementedError(f"{FAMILY}: no serving mesh")
        c = config
        attn_impl = resolve_paged_impl(attn_impl)
        one_row = jnp.ndim(start) == 0
        start, n_valid = jnp.reshape(start, (-1,)), jnp.reshape(n_valid, (-1,))
        T = tokens.shape[1]
        ps = cache.page_size
        t_idx = jnp.arange(T, dtype=jnp.int32)
        positions = start[:, None] + t_idx  # (B, T)
        write_pages = jnp.where(t_idx < n_valid[:, None], jnp.take_along_axis(page_table, positions // ps, axis=1),
                                cache.num_pages)  # pad rows: out of range, dropped
        offs = positions % ps
        # row t sees start + t + 1 keys; pad rows what the last valid row sees, an empty row one key
        counts = jnp.maximum(jnp.minimum(positions, (start + n_valid)[:, None] - 1) + 1, 1)
        col = jnp.arange(page_table.shape[1] * ps, dtype=jnp.int32)
        keep = col[None, None, None, :] < counts[:, None, :, None]  # (B, 1, T, S)

        def attend(pools, row, q, k, v):
            ck, cv, _, _ = _paged_write((*pools, None, None), row, write_pages, offs, k, v, attn_impl, None)
            kg = _gather_layer_kv(ck, None, row, page_table, q.dtype, c.head_dim)  # (B, H, S, C)
            vg = _gather_layer_kv(cv, None, row, page_table, q.dtype, c.head_dim)
            s = jnp.einsum("bthc,bhsc->bhts", q.astype(kg.dtype), kg).astype(jnp.float32) / math.sqrt(c.head_dim)
            prob = jax.nn.softmax(jnp.where(keep, s, float("-inf")), axis=-1).astype(vg.dtype)
            return jnp.einsum("bhts,bhsc->bthc", prob, vg), (ck, cv)

        h, _, (ck, cv) = Ouro._run(c, params, _embed(params, tokens), positions, cache.pools[0], attend)
        last = jnp.take_along_axis(h, jnp.maximum(n_valid - 1, 0)[:, None, None], axis=1)  # (B, 1, D)
        logits = Ouro._head(params, last)
        return (logits if one_row else logits[:, 0]), dataclasses.replace(cache, pools=((ck, cv),))
