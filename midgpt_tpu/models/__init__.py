"""Model families. A family is a config dataclass and a namespace of pure
functions over (config, params); the training runtime, the optimizer, the
serving engine and the entry points reach a model ONLY through what is listed
here, and call each without asking whether it is there. Families are
registered in `midgpt_tpu/config.py` `MODEL_FAMILIES`.

The config (`GPTConfig`, `KimiLinearConfig`, `MimoV2Config`, `PanguUltraConfig`,
`OuroConfig`, `TrinityConfig`, `Dots3Config`, `OlmoHybridConfig`,
`GraniteHybridConfig`):

    block_size, vocab_size, n_layer, n_head, n_embd   fields, under these names
                               (`n_layer`: layers of WEIGHTS; how many layers
                               the paged cache has is the family's to say, in
                               `init_cache`)
    model()                    -> the namespace below
    check_experiment(config)   raises ValueError for an ExperimentConfig this
                               family cannot run (mesh axes, schedules, knobs)
    check_training(who)        raises NotImplementedError where no backward is
                               wired for this family (`make_train_step` asks);
                               returns None where it trains
    check_serving(who)         raises NotImplementedError where the serving
                               stack (sample.py, ServeEngine) holds no cache
                               for this family; returns None where it does

The namespace (`GPT`, `KimiLinear`, `MimoV2`, `PanguUltra`, `Ouro`, `Trinity`, `Dots3`,
`OlmoHybrid`, `GraniteHybrid`):

    init(config, key) -> params
    hidden(config, params, tokens, *, key, inference, attn_fn) -> (B, T, D)
    count_params(params) -> int
    cast_params(params, dtype) -> the compute copy of the parameters
    weight_decay_mask          None (every leaf decays), or params -> tree of
                               bools, True where AdamW's decay applies
    param_specs(config, tree, mesh) -> PartitionSpec tree (config: the
                               ExperimentConfig)
    flops_per_token(config, seq_len=None, stats=None) -> FLOPs a token
                               (training's, 3 x forward; forward only for a
                               family that is served and not trained);
                               `stats`: what `route_stats` returned
    route_stats                None, or (config, params, tokens (B, T)) ->
                               {counter name: scalar}, forward only: the
                               counters the train loop logs at a logged step

The SERVING members, of every family whose `check_serving` returns None
(`GPT`, `MimoV2`, `PanguUltra`, `Ouro`, `Trinity`, `Dots3`, `OlmoHybrid`,
`GraniteHybrid`; sampling/serve.py calls them, never a family by name):

    cache_kinds(config) -> (CacheKind(name, window, sinks), ..., [StateKind(name, shapes)])
                               the kinds of cache the layers need, the PAGED
                               kinds first and the engine's first kind first.
                               Each paged kind gets a page table and an
                               allocator of its own; `window` > 0: a page is
                               freed once every future query's window has
                               passed it (0: it lives as long as its
                               request); `sinks`: leading tokens never freed.
                               A STATE kind is memory that is a ROW A SLOT and
                               not a row a token (a recurrent layer's state, as
                               large for ten tokens as for ten thousand);
                               `shapes(cache_dtype)` gives ((shape, dtype),
                               ...) of one slot's row. It has no pages, window
                               or table: the pool owner (sampling/pages.py
                               "State kinds") sizes it by the slot count (+ a
                               sink row), gives slot i row i, keeps it in the
                               conservation law and hands the programs each
                               table row's state row as the LAST entry of the
                               `page_table` tuple; what moves pages (prefix
                               cache, speculation, int8, a mesh, hot swap,
                               resize, spill, disaggregated hand-off) is
                               refused by name beside one. Which kinds a
                               family names, and what a row of each holds, is
                               in its own module docstring
    init_cache(config, num_pages, page_size, dtype, kernel_layout) -> cache
                               `num_pages[i]` pages for kind i (for a STATE
                               kind: its ROWS, the sink row included). Every
                               family but the GPT (`PagedKVCache`) answers
                               with ONE class, models/gpt.py `ServeCache`:
                               `pools[i]` the arrays of paged kind i, as many
                               as the kind needs, each (cache layers, pool
                               heads, pages, page_size, lanes), page 0 the
                               sink; `state` the state kind's arrays, the row
                               axis SECOND, the last row the sink; `counters`
                               the family's device-side counters. The family
                               hands `ServeCache.zeros` each array's (layers,
                               heads, width) and its counters, and holds
                               neither the layout, the kernel path's lane rule
                               nor the int8 refusal. The LAYER axis is the
                               family's, not `n_layer`: the engine sizes,
                               allocates and frees PAGES and never reads it.
                               The engine reads `pool_arrays()` (the layout
                               census), `page_size`, `num_pages`; the pool
                               owner reads `state`
    prefill_batched            True: `prefill_paged_chunk` takes the chunks of
                               B slots as the rows of one batch (the GPT;
                               Ouro: a call reads the layers' weights n_loop
                               times whatever rides it; Trinity: a call
                               streams every expert once whatever rides it.
                               Its two kinds of table are no obstacle: the
                               engine hands every kind's (B, pages) table and
                               frees window pages per slot after the call;
                               only the attention is per slot, and runs row
                               after row INSIDE the program). False:
                               one row a call, and the engine's
                               `prefill_width` is 1 (MimoV2: its cell has no
                               memory to spare for four rows' temporaries;
                               PanguUltra, Dots3: the sweeps over latents
                               that ROADMAP S13 / S17 rewrite; each its own
                               PR, ROADMAP S15)
    prefill_rows(config, dense_rows) -> int
                               (a `prefill_batched` family) token rows a
                               prefill call should carry, given the rows a
                               DENSE weight wants (`sampling/serve.py`
                               `PREFILL_ROWS`); the engine's `prefill_width`
                               is as many chunks. A family whose every weight
                               sees every row returns `dense_rows` (the GPT,
                               Ouro). A routed expert sees `top_k /
                               n_experts` of the rows: Trinity returns the
                               rows that bring an expert dense_rows / 2 pairs
                               on average (ops/moe.py `moe_prefill_rows`:
                               2,048 at 128 experts, top-8)
    prefill_paged_chunk(config, params, tokens (B, T), start (B,),
        n_valid (B,), cache, page_table (B, pages), attn_impl, mesh)
        -> (logits (B, V), cache)
                               row b: the chunk [start[b], start[b] +
                               n_valid[b]) of one slot, `page_table[b]` its
                               pages (the tuple of every kind's (B, pages)
                               where there are several); n_valid 0: an empty
                               place that writes nothing (and, in a routed
                               layer, takes no row among the experts':
                               `moe_serving`'s `valid`). logits: the row at
                               each slot's last valid position (all the
                               engine reads). A family with a state kind reads
                               each row's state from its state row and writes
                               back the state after its n_valid tokens; rows
                               past n_valid change nothing of it. A row whose
                               `start` is 0 (its prompt's first chunk) begins
                               from ZEROS whatever its state row holds: that
                               IS the row's reset for a request admitted to a
                               slot another has left (a stale page is masked
                               by the length, a stale row would be a wrong
                               answer); no program of the pool owner does it.
                               The ONE-ROW call, which every family takes and
                               an engine of width 1 makes (a family that is
                               not `prefill_batched`, or shapes that give 1):
                               tokens (1, T), SCALAR start / n_valid,
                               `page_table` the slot's (1, pages) row (the
                               tuple of every kind's where there are
                               several), logits (1, T, V), or (1, 1, V): the
                               last valid row's alone
    decode_step_paged(config, params, token (B,), cache, page_table, lengths,
        active, attn_impl, mesh, split_k) -> (logits (B, V), cache)
                               the batch is the slots in order (slot b's state
                               row is row b: `PagePool.tables` raises on any
                               other); an INACTIVE slot writes no key
                               and leaves its state row bit for bit (it may be
                               in the middle of its chunked prefill). The
                               engine's `next_logits` hands the state rows back
                               as they came (`pages.keep_state`): a family whose
                               rows are large writes them OUTSIDE the loop that
                               reads them, so that program holds no write and
                               no copy of the array (models/granite_hybrid.py)
    verify_step_paged          the speculative verify step with decode's
                               arguments over (B, k + 1) tokens, or None where
                               the family has none (the engine then refuses a
                               draft model). MimoV2: none over two kinds.
                               PanguUltra: none, because its published drafter
                               is a next-token-prediction layer that reads the
                               target's last hidden state, which is left out,
                               and the engine's draft model is a GPT. Ouro:
                               none. OlmoHybrid, GraniteHybrid: none (a
                               rejected draft would need the state it started
                               from: snapshots are not wired)
    kernel_sweep(config, cache) -> (pool shape, q rows a pool head, window,
                               sinks) of the decode kernel's sweep, for the
                               engine's block counters
    kernel_sweep_whole         True: that sweep is EVERY paged-attention
                               kernel call of the family's decode program
                               (layers that gather have no blocks), so a
                               round's two integers can ride its
                               `decode.dispatch` span as `blocks_swept` /
                               `blocks_live`. False (Trinity: global and
                               window layers both run the template, at two
                               geometries): the counters keep the one
                               kernel's figure, the span says nothing
    serve_counters             None, or (config, cache) -> {counter: number}
                               the family's own counters kept in the cache
                               (MimoV2, PanguUltra, Trinity, Dots3: the expert
                               layers', through ops/moe.py's shared helpers;
                               PanguUltra also the pool's bytes a token; Dots3
                               also each pool array's bytes a token and the
                               indexer's `dsa.*`: decoded tokens, index keys
                               scored, latent rows selected; Ouro: decode steps, passes
                               run, the exit gate's distribution summed over
                               decoded tokens, the pools' bytes a token over
                               all n_loop * n_layer cache layers; OlmoHybrid:
                               `gdn.decode_tokens`, `gdn.prefill_tokens`,
                               `gdn.prefill_chunks`, what its linear layers
                               took; GraniteHybrid: `ssm.decode_tokens`,
                               `ssm.prefill_tokens`, `ssm.prefill_chunks`, what
                               its mamba layers took; the pool owner adds
                               `state.*` to both), read on demand
"""

from midgpt_tpu.models.gpt import GPT, GPTConfig, GPTParams

__all__ = ["GPT", "GPTConfig", "GPTParams"]
