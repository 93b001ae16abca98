"""tools/migrate_ckpt_v2_v3.py: the v2 (flat head-major) -> v3 ((3,D,D))
wqkv permutation, verified end to end against a from-scratch construction."""

import importlib.util
import os
import sys

import jax
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "migrate_ckpt",
    os.path.join(os.path.dirname(__file__), "..", "tools", "migrate_ckpt_v2_v3.py"),
)
mig = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(mig)


def test_wqkv_permutation_matches_semantics():
    """Row r of the v2 layout holds head h=(r//(3C)), slot j=(r//C)%3,
    channel c=r%C; the migrated (3, D, D) must hold that row at
    [j, h*C + c]."""
    L, H, C, D = 2, 3, 4, 12
    rng = np.random.default_rng(0)
    v2 = rng.normal(size=(L, 3 * D, D)).astype(np.float32)
    out = mig.migrate_tree({"wqkv": v2}, n_head=H)["wqkv"]
    assert out.shape == (L, 3, D, D)
    for r in range(3 * D):
        h, j, c = r // (3 * C), (r // C) % 3, r % C
        np.testing.assert_array_equal(out[:, j, h * C + c], v2[:, r])


def test_migrate_tree_touches_only_wqkv():
    tree = {
        "blocks": {"attn": {"wqkv": np.zeros((1, 12, 4)), "wo": np.ones((1, 4, 4))}},
        "mu": {"blocks": {"attn": {"wqkv": np.zeros((1, 12, 4))}}},
    }
    out = mig.migrate_tree(tree, n_head=2)
    assert out["blocks"]["attn"]["wqkv"].shape == (1, 3, 4, 4)
    assert out["mu"]["blocks"]["attn"]["wqkv"].shape == (1, 3, 4, 4)
    np.testing.assert_array_equal(out["blocks"]["attn"]["wo"], np.ones((1, 4, 4)))


def test_migrate_checkpoint_end_to_end(tmp_path, monkeypatch):
    """Save a v2-format checkpoint (old flat layout + v2 marker), migrate via
    the CLI, and restore it through the current CheckpointManager."""
    from midgpt_tpu.training import checkpoint as ckpt_mod

    H, C = 2, 4
    D = H * C
    v2_params = {
        "blocks": {"attn": {"wqkv": np.arange(2 * 3 * D * D, dtype=np.float32).reshape(2, 3 * D, D)}}
    }
    v2_opt = {"mu": v2_params, "count": np.zeros(())}

    src = tmp_path / "src"
    monkeypatch.setattr(ckpt_mod, "FORMAT", {"version": 2, "qkv_layout": "head_major"})
    w = ckpt_mod.CheckpointManager(str(src), save_interval_steps=1)
    w.save(5, {"params": v2_params, "opt_state": v2_opt})
    w.wait()
    w.close()
    monkeypatch.undo()

    dst = tmp_path / "dst"
    # In-process (NOT a subprocess): conftest's CPU selection is
    # per-process, and a bare python child would pick its own backend.
    monkeypatch.setattr(
        sys, "argv", ["migrate", str(src), str(dst), "--n-head", str(H)]
    )
    mig.main()

    r = ckpt_mod.CheckpointManager(str(dst), save_interval_steps=1)
    like = {
        "params": {
            "blocks": {
                "attn": {
                    "wqkv": jax.ShapeDtypeStruct((2, 3, D, D), np.float32)
                }
            }
        }
    }
    restored = r.restore(5, like)  # v3 marker: restore must ACCEPT it
    r.close()
    got = np.asarray(restored["params"]["blocks"]["attn"]["wqkv"])
    want = mig.migrate_tree(v2_params, n_head=H)["blocks"]["attn"]["wqkv"]
    np.testing.assert_array_equal(got, want)
