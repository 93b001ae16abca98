"""Sample from a trained checkpoint (reference sample.py's surface, KV-cached).

    python sample.py --ckpt_dir=outputs/<run> [--start="\\n"|FILE:prompt.txt]
        [--num_samples=10] [--max_new_tokens=500] [--temperature=0.8] [--top_k=K] [--top_p=P]

Differences from the reference: decoding uses a static KV cache (one full
forward for the prompt, one single-token step per new token) instead of a
full padded forward per token (reference sample.py:68-95); and only the
model params item is restored from the checkpoint — no optimizer skeleton
reconstruction (reference sample.py:111-137) thanks to the named-item layout.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--ckpt_dir", type=str, required=True)
    parser.add_argument("--start", type=str, default="\n")
    parser.add_argument("--num_samples", type=int, default=10)
    parser.add_argument("--max_new_tokens", type=int, default=500)
    parser.add_argument("--temperature", type=float, default=0.8)
    parser.add_argument("--top_k", type=int, default=None)
    parser.add_argument("--top_p", type=float, default=None, help="nucleus sampling mass")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--engine",
        choices=("batch", "continuous"),
        default="batch",
        help="'batch': one fixed (num_samples, T) batch through the KV-cache "
        "loop; 'continuous': the paged continuous-batching server "
        "(sampling/serve.py) — each sample is an independent request, so "
        "mixed --max_new_tokens finish independently instead of padding to "
        "the longest (docs/SERVING.md)",
    )
    parser.add_argument(
        "--max_slots", type=int, default=4,
        help="continuous engine: concurrent decode slots",
    )
    parser.add_argument(
        "--spec_layers", type=int, default=None,
        help="speculative decoding with a SELF-DRAFT of this many leading "
        "layers (shared embeddings/lm_head, sampling/spec.py). Default: the "
        "checkpoint config's spec_layers (0 = off); 0 forces it off. "
        "Implies --engine=continuous",
    )
    parser.add_argument(
        "--kv_dtype", choices=("bf16", "int8"), default=None,
        help="paged KV cache storage dtype for the continuous engine "
        "(docs/SERVING.md 'Quantized KV cache'): int8 halves cache HBM "
        "and decode-attention traffic. Default: the checkpoint config's "
        "kv_cache_dtype. Implies --engine=continuous (the batch engine's "
        "contiguous cache has no quantized mode)",
    )
    parser.add_argument(
        "--draft_ckpt", type=str, default=None,
        help="speculative decoding with a SEPARATE draft checkpoint dir "
        "(its own config.json; must share vocab and block_size). Implies "
        "--engine=continuous; mutually exclusive with --spec_layers",
    )
    args = parser.parse_args()
    if args.draft_ckpt is not None and args.spec_layers:
        parser.error("--draft_ckpt and --spec_layers are mutually exclusive")
    if args.draft_ckpt is not None or args.spec_layers:
        args.engine = "continuous"  # speculation lives in the serve engine
    if args.kv_dtype == "int8":
        args.engine = "continuous"  # the quantized cache is paged-only

    import jax

    from midgpt_tpu.utils import compile_cache

    cache_stats = compile_cache.enable()

    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.config import from_json
    from midgpt_tpu.sampling.engine import check_batch_engine, generate, restore_for_sampling
    from midgpt_tpu.utils.precision import cast_floating

    config_path = os.path.join(args.ckpt_dir, "config.json")
    if args.ckpt_dir.startswith("gs://"):
        import gcsfs

        with gcsfs.GCSFileSystem().open(config_path, "r") as f:
            config = from_json(f.read())
    else:
        with open(config_path, "r") as f:
            config = from_json(f.read())
    model_cfg = config.model_config
    try:
        model_cfg.check_serving("sample.py")
        if args.engine != "continuous":
            check_batch_engine(model_cfg)
    except NotImplementedError as e:  # a model family the serving stack, or the engine asked for, does not hold
        raise SystemExit(str(e))
    print(config)

    # Restore just the "params" item, sharded over an inference mesh (all
    # local devices on 'fsdp' — the 7B-class checkpoints cannot restore to
    # one device; on a single chip this is the plain restore).
    try:
        params, step = restore_for_sampling(args.ckpt_dir, config)
    except FileNotFoundError as e:
        raise SystemExit(str(e))
    print(f"restored checkpoint step {step}")
    # the family's own compute copy (models/__init__.py): what it keeps in
    # float32 (a router, a sink logit) stays so
    params = model_cfg.model().cast_params(params, jnp.dtype(config.compute_dtype))

    # Tokenizer: dataset-shipped codec if present (char stoi/itos, or an
    # offline-trained HF BPE from data/local_text/prepare.py), else GPT-2 BPE
    # (reference sample.py:143-159).
    meta_path = os.path.join(config.data_dir, "meta.pkl")
    if os.path.exists(meta_path):
        with open(meta_path, "rb") as f:
            meta = pickle.load(f)
        if meta.get("kind") == "hf_bpe":
            from tokenizers import Tokenizer

            tok_path = os.path.join(config.data_dir, meta["tokenizer_file"])
            want_sha = meta.get("tokenizer_sha256")
            if want_sha is not None:
                import hashlib

                with open(tok_path, "rb") as tf:
                    got_sha = hashlib.sha256(tf.read()).hexdigest()
                if got_sha != want_sha:
                    raise ValueError(
                        f"{tok_path} does not match the tokenizer this "
                        "dataset (and any checkpoint trained on it) was "
                        "built with — decoding would be silently wrong. "
                        "Re-run the dataset's prepare.py."
                    )
            tok = Tokenizer.from_file(tok_path)
            encode = lambda s: tok.encode(s).ids
            decode = lambda ids: tok.decode(ids, skip_special_tokens=False)
        else:
            stoi, itos = meta["stoi"], meta["itos"]
            encode = lambda s: [stoi[c] for c in s]
            decode = lambda ids: "".join(itos[i] for i in ids)
    else:
        import tiktoken

        try:
            # fetches the GPT-2 vocabulary over the network unless cached
            enc = tiktoken.get_encoding("gpt2")
        except Exception as e:
            raise SystemExit(
                f"no tokenizer: data_dir={config.data_dir!r} holds no "
                "meta.pkl and the GPT-2 vocabulary could not be fetched "
                f"({type(e).__name__}); put the dataset's meta.pkl (and "
                "tokenizer.json) there"
            ) from None
        encode = lambda s: enc.encode(s, allowed_special={"<|endoftext|>"})
        decode = enc.decode

    start = args.start
    if start.startswith("FILE:"):
        with open(start[5:], "r", encoding="utf-8") as f:
            start = f.read()
    start_ids = encode(start if start != "" else "\n")
    prompt = np.tile(np.asarray(start_ids, np.int32), (args.num_samples, 1))

    if args.engine == "continuous":
        from midgpt_tpu.sampling.serve import ServeEngine

        draft_config = draft_params = None
        draft_shares_cache = False
        spec_layers = (
            config.spec_layers if args.spec_layers is None else args.spec_layers
        )
        if args.draft_ckpt is not None:
            # Separate small draft model: restore its own checkpoint; the
            # rejection sampler only needs matching output spaces.
            with open(os.path.join(args.draft_ckpt, "config.json")) as f:
                draft_exp = from_json(f.read())
            draft_config = draft_exp.model_config
            draft_params, draft_step = restore_for_sampling(
                args.draft_ckpt, draft_exp
            )
            draft_params = cast_floating(
                draft_params, jnp.dtype(config.compute_dtype)
            )
            print(f"draft checkpoint step {draft_step} ({args.draft_ckpt})")
        elif spec_layers:
            from midgpt_tpu.sampling.spec import self_draft

            draft_config, draft_params = self_draft(
                model_cfg, params, spec_layers
            )
            draft_shares_cache = True  # prefix layers ride the target pool
            print(f"self-draft: first {spec_layers}/{model_cfg.n_layer} layers")
        kv_dtype = (
            config.kv_cache_dtype if args.kv_dtype is None else args.kv_dtype
        )
        eng = ServeEngine(
            model_cfg,
            params,
            max_slots=args.max_slots,
            cache_dtype=kv_dtype,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            seed=args.seed,
            draft_params=draft_params,
            draft_config=draft_config,
            draft_shares_cache=draft_shares_cache,
            spec_k_max=config.spec_k_max,
            spec_k_min=config.spec_k_min,
            spec_adapt=config.spec_adapt,
        )
        uids = [
            eng.submit(prompt[i], args.max_new_tokens)
            for i in range(args.num_samples)
        ]
        finished = eng.run()
        out = [finished[u].tokens for u in uids]
        if draft_params is not None:
            s = eng.spec_stats()
            print(
                f"speculative: accept_rate {s['accept_rate']:.2f}, "
                f"tokens/verify {s['tokens_per_verify']:.2f}"
            )
    else:
        out = generate(
            model_cfg,
            params,
            prompt,
            args.max_new_tokens,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            key=jax.random.PRNGKey(args.seed),
        )
    for i in range(args.num_samples):
        print(decode(np.asarray(out[i]).tolist()))
        print("---------------")
    # machine-readable: the ids after the prompt, one list per sample
    new_tokens = [np.asarray(o).tolist()[len(start_ids):] for o in out]
    print("new_tokens: " + json.dumps(new_tokens))
    if args.engine == "continuous":
        # per compiled serving program: pool- or layer-sized copies in its
        # compiled text (0 = the pool kept one layout), and instructions that
        # write a layer's weight matrix out again (0 = every matmul reached
        # its weight in place); empty off the TPU
        census = ServeEngine.compile_stats(census=True)
        print("pool_relayouts: " + json.dumps(census["pool_relayouts"]))
        print("weight_copies: " + json.dumps(census["weight_copies"]))
    print(cache_stats.summary())


if __name__ == "__main__":
    main()
