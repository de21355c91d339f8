"""Native (C++) components, loaded via ctypes with pure-Python fallbacks.

The only native code the reference runs in its data path is the HF fast
tokenizer; its concat-and-chunk grouping loop is Python (ref:
picotron/data.py:57-100). Here the grouping loop is `BlockPacker`, a C++
streaming packer compiled on first use (g++ is part of the toolchain; no
pybind11 — plain C ABI + ctypes). If compilation is impossible the
`PyBlockPacker` fallback provides identical behavior.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

import numpy as np

_THIS_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_THIS_DIR, "packer.cpp")

_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _lib_path() -> str:
    """The built library is named after its source's content, so "is it
    there" is also "is it current" — a checkout or a copy preserves no
    mtimes, and the artifact is never in git."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_THIS_DIR, f"libpacker-{digest}.so")


def _ensure_built() -> Optional[ctypes.CDLL]:
    """Compile packer.cpp -> libpacker-<source hash>.so if absent; load it."""
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    try:
        lib_path = _lib_path()
        if not os.path.exists(lib_path):
            tmp = f"{lib_path}.{os.getpid()}.tmp"  # concurrent first users
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", _SRC, "-o", tmp],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, lib_path)
        lib = ctypes.CDLL(lib_path)
        lib.packer_new.restype = ctypes.c_void_p
        lib.packer_new.argtypes = [ctypes.c_int64]
        lib.packer_free.argtypes = [ctypes.c_void_p]
        lib.packer_feed.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_int32),
                                    ctypes.c_int64]
        lib.packer_num_ready.restype = ctypes.c_int64
        lib.packer_num_ready.argtypes = [ctypes.c_void_p]
        lib.packer_carry_len.restype = ctypes.c_int64
        lib.packer_carry_len.argtypes = [ctypes.c_void_p]
        lib.packer_take.restype = ctypes.c_int64
        lib.packer_take.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_int32),
                                    ctypes.c_int64]
        _lib = lib
        return _lib
    except Exception:
        _build_failed = True
        return None


class BlockPacker:
    """Streaming fixed-size token-block packer (C++ backed).

    feed() token-id arrays of any length; take() returns completed
    [n, block_size] int32 blocks. The partial tail carries across feeds, so
    document streams pack losslessly across batch boundaries.
    """

    def __init__(self, block_size: int):
        if block_size <= 0:
            raise ValueError(f"block_size must be positive, got {block_size}")
        self.block_size = block_size
        lib = _ensure_built()
        if lib is None:
            raise RuntimeError(
                "native packer unavailable (g++ build failed); use "
                "PyBlockPacker")
        self._lib = lib
        self._h = lib.packer_new(block_size)

    def feed(self, tokens) -> None:
        arr = np.ascontiguousarray(tokens, dtype=np.int32)
        if arr.size == 0:
            return
        self._lib.packer_feed(
            self._h, arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            arr.size)

    @property
    def num_ready(self) -> int:
        return self._lib.packer_num_ready(self._h)

    @property
    def carry_len(self) -> int:
        return self._lib.packer_carry_len(self._h)

    def take(self, max_blocks: Optional[int] = None) -> np.ndarray:
        n = self.num_ready
        if max_blocks is not None:
            n = min(n, max_blocks)
        out = np.empty((n, self.block_size), dtype=np.int32)
        if n:
            got = self._lib.packer_take(
                self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n)
            assert got == n
        return out

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.packer_free(h)
            self._h = None


class PyBlockPacker:
    """Pure-numpy fallback with BlockPacker's exact contract."""

    def __init__(self, block_size: int):
        if block_size <= 0:
            raise ValueError(f"block_size must be positive, got {block_size}")
        self.block_size = block_size
        self._carry = np.empty((0,), dtype=np.int32)
        self._blocks: list[np.ndarray] = []

    def feed(self, tokens) -> None:
        arr = np.ascontiguousarray(tokens, dtype=np.int32).ravel()
        buf = np.concatenate([self._carry, arr]) if self._carry.size else arr
        n = buf.size // self.block_size
        if n:
            self._blocks.append(
                buf[:n * self.block_size].reshape(n, self.block_size).copy())
        self._carry = buf[n * self.block_size:].copy()

    @property
    def num_ready(self) -> int:
        return sum(b.shape[0] for b in self._blocks)

    @property
    def carry_len(self) -> int:
        return int(self._carry.size)

    def take(self, max_blocks: Optional[int] = None) -> np.ndarray:
        avail = np.concatenate(self._blocks) if self._blocks else np.empty(
            (0, self.block_size), dtype=np.int32)
        n = avail.shape[0] if max_blocks is None else min(avail.shape[0],
                                                          max_blocks)
        out = avail[:n]
        rest = avail[n:]
        self._blocks = [rest] if rest.size else []
        return out


def make_packer(block_size: int):
    """BlockPacker if the native library builds/loads, else PyBlockPacker."""
    try:
        return BlockPacker(block_size)
    except (RuntimeError, OSError):
        return PyBlockPacker(block_size)
