"""Token-stream dataset: uint16 memmap bins + seeded random-window sampling.

Format-compatible with the reference/nanoGPT pipeline: `train.bin`/`val.bin`
flat uint16 token streams, plus optional `meta.pkl` char codec (reference
train.py:56-66,132-137; data/*/prepare.py).

Two deliberate upgrades over the reference:
  * **Seeded, resumable sampling.** The reference draws from the unseeded
    global numpy RNG (reference train.py:60), so resumed runs replay nothing.
    Here every batch is drawn from `np.random.default_rng([seed, split, step])`
    — stateless, deterministic, and exactly replayable after restore with no
    sampler state to checkpoint.
  * **Optional RAM copy.** The reference always copies the full 17GB stream
    into host RAM (train.py:132-133). `in_ram=False` keeps the memmap and
    lets the page cache do its job.
"""

from __future__ import annotations

import os
import pickle
import typing as tp

import numpy as np

from midgpt_tpu.obs import flight_recorder

_SPLIT_IDS = {"train": 0, "val": 1}


def sample_batch(
    data: np.ndarray,
    block_size: int,
    batch_size: int,
    g_accum_iters: tp.Optional[int] = None,
    *,
    rng: tp.Optional[np.random.Generator] = None,
    accum_slice: tp.Optional[tp.Tuple[int, int]] = None,
) -> tp.Tuple[np.ndarray, np.ndarray]:
    """Random (x, y=x shifted by one) windows, int32.

    Shapes: (B, T) or (G, B, T) when g_accum_iters is given (reference
    train.py:56-66).

    accum_slice=(lo, m) materializes only accumulation steps [lo, lo+m) of
    the full (g_accum_iters, B, T) draw: ALL window starts are generated (a
    cheap rng.integers pass) and then sliced, so chunked consumers (the
    memory-bounded evaluate loop) see bit-identical windows to a monolithic
    caller."""
    rng = rng or np.random.default_rng()
    bs = batch_size * (g_accum_iters or 1)
    starts = rng.integers(0, len(data) - block_size, size=(bs,))
    if accum_slice is not None:
        assert g_accum_iters is not None
        lo, m = accum_slice
        starts = starts[lo * batch_size : (lo + m) * batch_size]
        g_accum_iters = m
    # One-pass native gather when the C batcher is available (built on
    # demand, midgpt_tpu/native); numpy double-gather otherwise. The RNG
    # stays in numpy either way, so both paths are bit-identical.
    from midgpt_tpu import native

    xy = native.sample_windows(data, starts, block_size)
    if xy is not None:
        x, y = xy
    else:
        offsets = np.arange(block_size)
        x = data[starts[:, None] + offsets].astype(np.int32)
        y = data[starts[:, None] + offsets + 1].astype(np.int32)
    if g_accum_iters is not None:
        x = x.reshape(g_accum_iters, batch_size, block_size)
        y = y.reshape(g_accum_iters, batch_size, block_size)
    return x, y


class TokenDataset:
    """train/val uint16 streams from `data_dir`, sliced per host."""

    def __init__(
        self,
        data_dir: str,
        *,
        in_ram: bool = True,
        seed: int = 1337,
        shard_by_process: bool = False,
    ):
        """shard_by_process: give this host a contiguous 1/n_proc slice of
        EACH split (sized per split — reference train.py:122-136)."""
        self.data_dir = data_dir
        self.seed = seed
        self.splits: tp.Dict[str, np.ndarray] = {}
        # Prep pipelines that retrain their tokenizer (data/local_text)
        # fingerprint the bins in meta.pkl; bins left behind from an older
        # prepare run would otherwise train silently on re-interpreted ids.
        expected = (self.meta() or {}).get("split_tokens", {})
        for split in ("train", "val"):
            path = os.path.join(data_dir, f"{split}.bin")
            arr = np.memmap(path, dtype=np.uint16, mode="r")
            if expected.get(split, len(arr)) != len(arr):
                raise ValueError(
                    f"{path} has {len(arr):,} tokens but meta.pkl records "
                    f"{expected[split]:,} — the bins predate the committed "
                    "tokenizer/meta. Re-run the dataset's prepare.py."
                )
            if shard_by_process:
                import jax

                n_proc, idx = jax.process_count(), jax.process_index()
                # Equal-length contiguous slices (remainder tokens dropped) so
                # every process samples from the same-sized pool.
                per = len(arr) // n_proc
                arr = arr[idx * per : (idx + 1) * per]
            if in_ram:
                arr = np.ascontiguousarray(arr)
            self.splits[split] = arr

    def __getitem__(self, split: str) -> np.ndarray:
        return self.splits[split]

    def batch(
        self,
        split: str,
        step: int,
        block_size: int,
        batch_size: int,
        g_accum_iters: tp.Optional[int] = None,
        accum_slice: tp.Optional[tp.Tuple[int, int]] = None,
    ) -> tp.Tuple[np.ndarray, np.ndarray]:
        """Deterministic batch for (split, step): resumable by construction.
        Host batch assembly is the `data.batch` span of the flight recorder:
        opened here, so every loop that feeds a step passes through it."""
        with flight_recorder().tracer.span("data.batch", "data", "train"):
            rng = np.random.default_rng([self.seed, _SPLIT_IDS[split], step])
            return sample_batch(
                self.splits[split], block_size, batch_size, g_accum_iters,
                rng=rng, accum_slice=accum_slice,
            )

    def meta(self) -> tp.Optional[dict]:
        """Char-codec metadata if present (shakespeare_char)."""
        path = os.path.join(self.data_dir, "meta.pkl")
        if not os.path.exists(path):
            return None
        with open(path, "rb") as f:
            return pickle.load(f)
