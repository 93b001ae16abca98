"""kernels: how much of the (T, T) causal score matrix the flash-attention
kernels FORM a call, as a share of all of it (100 % = every tile, masked or
not; 50 % is the causal limit). A count from shapes, not a time:
`kernels/flash_attention.py score_tile_share` at the model's (T, blocks), which
`training/train.py make_runtime` works out once, prints, sets as the gauge
`attn.score_tile_share` and keeps on the runtime. `flash_attention_roofline`
divides CAUSAL FLOPs (half the matrix) by the kernels' time, so at a share of
s the kernels do 2 s times the work they are credited with. A program whose
runtime carries no such share (the parent of PR 40) reports nothing."""


def read(run):
    if run["kind"] != "train":
        return None
    import importlib

    # by module path: the package re-exports a FUNCTION called `train`
    train = importlib.import_module("midgpt_tpu.training.train")
    rt = getattr(train, "last_runtime", lambda: None)()
    share = getattr(rt, "attn_score_tile_share", None)
    if share is None:
        run["log"]("flash_tile_share: the runtime carries no attn_score_tile_share "
                   "(a program from before PR 40, or attn_impl is not 'flash'); left out")
        return None
    return {"flash_attention_tile_share": 100.0 * share}
