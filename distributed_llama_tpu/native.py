"""ctypes bindings for the native host library (native/libdllama_native.so).

The compute path is JAX/XLA/Pallas; this library covers the *host* hot paths
around it — Q40 repacking/dequantization at weight-load time and BPE encode —
the same split the reference makes between its engine and its loaders.

The library is OPTIONAL where no toolchain exists (no ``make`` on PATH:
every caller takes the numpy/Python implementation, so the package works
from a clean checkout). Where a toolchain exists the library is built from
the sources in ``native/`` and a build that fails raises — a broken build
never silently becomes the slow path. :func:`available` says which side
serves.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import shutil
import subprocess

import numpy as np

_LIB_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_LIB_DIR, "libdllama_native.so")
# content hash of the sources the binary was built from, written next to it
_KEY_PATH = _LIB_PATH + ".key"

_lib = None
_load_attempted = False


def _source_key() -> str:
    """Hash of everything the binary depends on (sources + Makefile: the
    flags are part of the ABI too). Content, not mtime: a copied tree keeps
    neither the times nor any promise that the .so beside the sources was
    built from them."""
    h = hashlib.sha256()
    for f in sorted(os.listdir(_LIB_DIR)):
        if f.endswith((".cpp", ".h", ".hpp")) or f == "Makefile":
            h.update(f.encode())
            with open(os.path.join(_LIB_DIR, f), "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def _stale() -> bool:
    """The built library does not match the sources (e.g. a checkout built
    before an ABI change, or a binary copied in from elsewhere): calling
    through a new prototype into an old binary corrupts memory, so rebuild
    first."""
    try:
        with open(_KEY_PATH) as fh:
            return fh.read().strip() != _source_key()
    except OSError:
        return True


def _build() -> None:
    """``make -C native`` from clean; raises with the compiler's output on
    failure."""
    for stale in (_LIB_PATH, _KEY_PATH):
        if os.path.exists(stale):
            os.remove(stale)
    proc = subprocess.run(
        ["make", "-C", _LIB_DIR], capture_output=True, text=True, timeout=300,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"building {_LIB_PATH} failed (rc={proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )
    with open(_KEY_PATH, "w") as fh:
        fh.write(_source_key())


def load_library(build: bool = True):
    """Returns the loaded library, or None where there is no toolchain to
    build it with (or ``build`` is False and no current binary exists).
    Rebuilds when the binary does not match the sources' content hash."""
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    # one builder at a time: test workers and sibling processes share the
    # checkout, and a half-written binary must never be loaded
    with open(_LIB_PATH + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not os.path.exists(_LIB_PATH) or _stale():
            if not build or shutil.which("make") is None:
                return None
            _build()
        lib = ctypes.CDLL(_LIB_PATH)

    lib.q40_dequant_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.q40_repack_tpu.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.bpe_new.restype = ctypes.c_void_p
    lib.bpe_new.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
    ]
    lib.bpe_free.argtypes = [ctypes.c_void_p]
    lib.bpe_encode.restype = ctypes.c_int32
    lib.bpe_encode.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
    ]
    _lib = lib
    return _lib


def available() -> bool:
    return load_library() is not None


# ---------------------------------------------------------------------------
# Q40
# ---------------------------------------------------------------------------


def q40_dequant_f32(blocks: np.ndarray, n_values: int) -> np.ndarray | None:
    """Dequantize raw Q40 file bytes → f32 [n_values]; None if lib missing."""
    lib = load_library()
    if lib is None:
        return None
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
    out = np.empty(n_values, np.float32)
    lib.q40_dequant_f32(
        blocks.ctypes.data, n_values // 32, out.ctypes.data
    )
    return out


def q40_repack_tpu(blocks: np.ndarray, d_out: int, d_in: int, n_pad: int):
    """Repack raw Q40 file bytes to the half-split layout: (packed
    [n_pad/2, d_out] uint8, scales [n_pad/32, d_out] f32 with zero-scale
    padding rows); None if lib missing. ``n_pad`` is the caller's padded
    input dim (ops.q40._n_padded — the padding rule lives there, once)."""
    lib = load_library()
    if lib is None:
        return None
    if n_pad % 64 or n_pad < d_in:
        raise ValueError(f"n_pad {n_pad} must be a 64-multiple >= d_in {d_in}")
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
    packed = np.zeros((n_pad // 2, d_out), np.uint8)  # OR-accumulated
    scales = np.zeros((n_pad // 32, d_out), np.float32)  # padding rows stay 0
    lib.q40_repack_tpu(
        blocks.ctypes.data, d_out, d_in, n_pad, packed.ctypes.data, scales.ctypes.data
    )
    return packed, scales


# ---------------------------------------------------------------------------
# BPE
# ---------------------------------------------------------------------------


class NativeBpe:
    """Owns a native tokenizer handle; mirrors Tokenizer.encode's core loop."""

    def __init__(self, vocab: list[bytes], scores: list[float]):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        blob = b"".join(vocab)
        offsets = np.zeros(len(vocab) + 1, np.int64)
        np.cumsum([len(t) for t in vocab], out=offsets[1:])
        self._blob = np.frombuffer(blob, np.uint8).copy()
        scores_arr = np.asarray(scores, np.float32)
        self._handle = lib.bpe_new(
            self._blob.ctypes.data,
            offsets.ctypes.data,
            scores_arr.ctypes.data,
            len(vocab),
        )

    def encode(self, text: bytes) -> list[int]:
        out = np.empty(len(text) + 1, np.int32)
        n = self._lib.bpe_encode(self._handle, text, len(text), out.ctypes.data)
        return out[:n].tolist()

    def __del__(self):
        if getattr(self, "_handle", None):
            self._lib.bpe_free(self._handle)
            self._handle = None
