"""The SAMPLED token streams of a seed are the parent's, bit for bit.

PR 37 moved the engine's sampling key onto the device: every sampled serving
program takes the engine's key, makes as its first operation the split the
host made before the call (sampling/serve.py `_split_key`) and hands back the
key the engine keeps. The sequence of keys is the one the host's splits gave,
in the order the programs were called, so the tokens a seed samples may not
move on any path: the classic round, `overlap="group"`, `overlap="double"`,
the speculative round (draft + verify, the three-way split) and the width-1
family call (a toy `mimo_v2`, two kinds of page table).

The golden (`golden/sampled_streams_parent.json`) was recorded on the PARENT
tree of PR 37 (commit bef1e18, where `ServeEngine` calls `jax.random.split`
on the host before every sampled program) by this file run as a script there:

    JAX_PLATFORMS=cpu python tests/test_sampled_streams.py > tests/golden/sampled_streams_parent.json

Temperature 0.8, two seeds a path, a ramp of requests over three slots on a
pool short enough to queue. The greedy golden beside it
(`golden/prefill_round_width1_parent.json`) holds what `temperature=0.0`
emits."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "golden", "sampled_streams_parent.json")
CASES = ("classic", "group", "double", "spec", "mimo_width1")
SEEDS = (0, 1234567)


def record(case: str) -> dict:
    """{seed: {uid: tokens}} of the ramp served at temperature 0.8 on `case`'s engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.sampling.serve import ServeEngine

    kw = dict(max_slots=3, page_size=8, num_pages=43, prefill_chunk=16, decode_chunk=4,
              temperature=0.8, top_k=40, cache_dtype=jnp.float32)
    work = [(25, 9), (5, 12), (34, 17), (11, 6), (47, 13)]
    if case == "mimo_width1":
        from test_mimo_v2 import toy
        from midgpt_tpu.models.mimo_v2 import MimoV2

        cfg = toy()
        params = MimoV2.init(cfg, jax.random.PRNGKey(0))
        kw.update(page_size=4, num_pages=60, prefill_chunk=8, top_k=None)
        work = [(37, 6), (5, 5), (50, 8), (11, 4), (23, 9)]
    else:
        from midgpt_tpu.models.gpt import GPT, GPTConfig
        from midgpt_tpu.sampling.spec import self_draft

        # widths no other test file serves: nothing here warms a pinned program set
        cfg = GPTConfig(block_size=64, vocab_size=83, n_layer=3, n_head=2, n_embd=32)
        params = GPT.init(cfg, jax.random.PRNGKey(0))
        if case == "group":
            kw.update(overlap="group", round_group=2)
        elif case == "double":
            kw.update(overlap="double", round_group=2)
        elif case == "spec":
            dcfg, dparams = self_draft(cfg, params, 1)
            kw.update(draft_params=dparams, draft_config=dcfg, spec_k_max=4, top_p=0.95)
    out = {}
    for seed in SEEDS:
        eng = ServeEngine(cfg, params, seed=seed, **kw)
        rng = np.random.default_rng(7)
        for n, m in work:
            eng.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32), m)
        done = eng.run()
        out[str(seed)] = {str(uid): np.asarray(r.tokens).tolist() for uid, r in sorted(done.items())}
    return out


@pytest.mark.parametrize("case", CASES)
def test_sampled_streams_are_the_parents_bit_for_bit(case):
    sys.path.insert(0, HERE)  # test_mimo_v2.toy
    got, want = record(case), json.load(open(GOLDEN))[case]
    assert len(want) == len(SEEDS) and all(len(v) == 5 for v in want.values())
    assert want[str(SEEDS[0])] != want[str(SEEDS[1])]  # sampled: the seed shows
    assert got == want


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.dirname(HERE))
    import jax

    jax.config.update("jax_platforms", "cpu")  # as tests/conftest.py sets the process up
    jax.config.update("jax_num_cpu_devices", 8)
    jax.config.update("jax_threefry_partitionable", True)
    jax.config.update("jax_default_matmul_precision", "highest")
    print(json.dumps({case: record(case) for case in CASES}, sort_keys=True))
