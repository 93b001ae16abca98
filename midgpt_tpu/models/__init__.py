"""Model families. A family is a config dataclass and a namespace of pure
functions over (config, params); the training runtime, the optimizer and the
entry points reach a model ONLY through what is listed here, and call each
without asking whether it is there. Families are registered in
`midgpt_tpu/config.py` `MODEL_FAMILIES`.

The config (`GPTConfig`, `KimiLinearConfig`):

    block_size, vocab_size, n_layer, n_head, n_embd   fields, under these names
    model()                    -> the namespace below
    check_experiment(config)   raises ValueError for an ExperimentConfig this
                               family cannot run (mesh axes, schedules, knobs)
    check_serving(who)         raises NotImplementedError where the serving
                               stack (sample.py, ServeEngine) holds no cache
                               for this family; returns None where it does

The namespace (`GPT`, `KimiLinear`):

    init(config, key) -> params
    hidden(config, params, tokens, *, key, inference, attn_fn) -> (B, T, D)
    count_params(params) -> int
    cast_params(params, dtype) -> the compute copy of the parameters
    weight_decay_mask          None (every leaf decays), or params -> tree of
                               bools, True where AdamW's decay applies
    param_specs(config, tree, mesh) -> PartitionSpec tree (config: the
                               ExperimentConfig)
    flops_per_token(config, seq_len=None, stats=None) -> training FLOPs a
                               token; `stats`: what `route_stats` returned
    route_stats                None, or (config, params, tokens (B, T)) ->
                               {counter name: scalar}, forward only: the
                               counters the train loop logs at a logged step
"""

from midgpt_tpu.models.gpt import GPT, GPTConfig, GPTParams

__all__ = ["GPT", "GPTConfig", "GPTParams"]
