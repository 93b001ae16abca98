"""Native host-runtime components (C, loaded via ctypes).

The TPU compute path is JAX/XLA/Pallas; the host runtime around it is where
native code earns its keep. Currently: the data batcher (batcher.c) — the
only host-side work on the training hot loop.

The shared library is built on demand with the system C compiler into this
package directory, once, at first use, under a name keyed by the hash of
batcher.c (`_batcher.<sha12>.so`): a library built from another source —
or copied in from another machine — is never loaded. No pybind11 and no
build-system hook: ctypes + cc keeps the extension working from a plain
checkout (and cross-compiles trivially on TPU-VM hosts via setup_hosts.sh).
Every entry point falls back to the numpy implementation when the toolchain
or the build is unavailable — host code, bit-identical (parity is asserted
in tests/test_native_batcher.py) — and the first use says on stderr which
of the two is serving, so the fallback is never silent.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import sysconfig
import threading
import typing as tp

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "batcher.c")

_lock = threading.Lock()
_lib: tp.Optional[ctypes.CDLL] = None
_build_failed = False


def _compiler() -> str:
    return os.environ.get("CC") or sysconfig.get_config_var("CC") or "cc"


def _lib_path() -> str:
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"_batcher.{digest}.so")


def _load() -> tp.Optional[ctypes.CDLL]:
    """Build (once) and load the shared library; None if unavailable."""
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            lib_path = _lib_path()
            if not os.path.exists(lib_path):
                cc = _compiler().split()[0]
                # Build to a per-process temp name, then publish atomically:
                # concurrent importers (pytest -n, parallel launches) must
                # never dlopen a half-written library.
                tmp = f"{lib_path}.{os.getpid()}.tmp"
                subprocess.run(
                    [cc, "-O3", "-shared", "-fPIC", "-pthread", _SRC, "-o", tmp],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
                os.replace(tmp, lib_path)
            lib = ctypes.CDLL(lib_path)
            lib.sample_windows.argtypes = [
                ctypes.c_void_p,  # data (uint16*)
                ctypes.c_int64,  # n_windows
                ctypes.c_int64,  # t
                ctypes.c_void_p,  # starts (int64*)
                ctypes.c_void_p,  # x_out (int32*)
                ctypes.c_void_p,  # y_out (int32*)
                ctypes.c_int64,  # n_threads
            ]
            lib.sample_windows.restype = None
            _lib = lib
            print(f"data batcher: native ({lib_path})", file=sys.stderr)
        except (OSError, subprocess.SubprocessError) as e:
            _build_failed = True
            print(
                f"data batcher: numpy (native build unavailable: "
                f"{type(e).__name__}: {e})",
                file=sys.stderr,
            )
    return _lib


def native_available() -> bool:
    return _load() is not None


def sample_windows(
    data: np.ndarray,  # uint16 token stream (memmap or RAM)
    starts: np.ndarray,  # int64 window starts, shape (n_windows,)
    block_size: int,
    n_threads: tp.Optional[int] = None,
) -> tp.Optional[tp.Tuple[np.ndarray, np.ndarray]]:
    """(x, y) int32 windows via the C kernel; None if the library is
    unavailable or inputs don't qualify (caller falls back to numpy)."""
    lib = _load()
    if lib is None or data.dtype != np.uint16:
        return None
    data = np.ascontiguousarray(data) if not data.flags.c_contiguous else data
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    n = int(starts.shape[0])
    if n and (starts.min() < 0 or int(starts.max()) + block_size >= len(data)):
        # same failure mode as the numpy fancy-indexing path it replaces —
        # the C kernel itself does not bounds-check
        raise IndexError(
            f"window out of bounds: starts in [{starts.min()}, {starts.max()}] "
            f"+ {block_size} vs stream of {len(data)} tokens"
        )
    x = np.empty((n, block_size), np.int32)
    y = np.empty((n, block_size), np.int32)
    if n_threads is None:
        n_threads = min(8, os.cpu_count() or 1)
    lib.sample_windows(
        data.ctypes.data_as(ctypes.c_void_p),
        n,
        block_size,
        starts.ctypes.data_as(ctypes.c_void_p),
        x.ctypes.data_as(ctypes.c_void_p),
        y.ctypes.data_as(ctypes.c_void_p),
        int(n_threads),
    )
    return x, y
