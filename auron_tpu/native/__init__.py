"""ctypes bindings for the host-side C++ kernels (native/auron_host.cc).

Built on first use with a numpy fallback: environments without a
toolchain still run, native just accelerates (the reference's equivalent
layer is mandatory Rust; here XLA is the compute path and this covers
host-runtime hot spots: spill-merge ordering and row gathers).

The library is compiled ``-march=native`` and git-ignored, so a binary
is only ever valid on the machine and for the source that built it: it
is rebuilt whenever it is missing or older than ``auron_host.cc``, and
``status()`` says which path this process ended up on and why."""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger("auron_tpu.native")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libauron_host.so")
_SRC_PATH = os.path.join(_NATIVE_DIR, "auron_host.cc")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
#: ("built" | "loaded" | "numpy", why) once _load() has run
_status: tuple[str, str] = ("numpy", "not loaded yet")


def _stale() -> Optional[str]:
    """Why the shared library must be (re)built, or None when the one on
    disk is at least as new as its source."""
    if not os.path.exists(_SO_PATH):
        return "library missing"
    if os.path.getmtime(_SO_PATH) < os.path.getmtime(_SRC_PATH):
        return "library older than auron_host.cc"
    return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _status
    with _lock:
        if _tried:
            return _lib
        _tried = True
        why = _stale()
        if why is not None:
            try:
                subprocess.run(["make", "-C", _NATIVE_DIR, "-s"],
                               check=True, capture_output=True, timeout=120)
            except (OSError, subprocess.SubprocessError) as e:
                detail = getattr(e, "stderr", b"") or b""
                _status = ("numpy", f"{why}; build failed: {e} "
                           f"{detail.decode(errors='replace')[-200:]}"
                           .strip())
                logger.warning("native build failed, using numpy "
                               "fallback: %s", _status[1])
                return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as e:
            _status = ("numpy", f"load failed: {e}")
            logger.warning("native load failed, using numpy fallback: %s", e)
            return None

        u64p = ctypes.POINTER(ctypes.c_uint64)
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.at_lex_sort_words.argtypes = [u64p, ctypes.c_int64,
                                          ctypes.c_int64, i32p]
        lib.at_merge_runs.argtypes = [u64p, i64p, ctypes.c_int64,
                                      ctypes.c_int64, i32p]
        lib.at_take_rows.argtypes = [u8p, i32p, ctypes.c_int64,
                                     ctypes.c_int64, u8p]
        lib.at_version.restype = ctypes.c_int64
        if lib.at_version() != 1:
            _status = ("numpy", "ABI version mismatch")
            logger.warning("native ABI mismatch, using numpy fallback")
            return None
        _status = (("built", why) if why is not None
                   else ("loaded", "library not older than auron_host.cc"))
        _lib = lib
        return _lib


def status() -> tuple[str, str]:
    """(path, why): ``built`` (compiled by this process), ``loaded`` (an
    up-to-date library was already on disk) or ``numpy`` (the fallback),
    with the reason."""
    _load()
    return _status


def available() -> bool:
    return _load() is not None


def _as_u64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def lex_sort_words(words: np.ndarray) -> np.ndarray:
    """Stable permutation sorting rows of a [n, w] uint64 word matrix
    lexicographically (most significant word first). Native radix sort when
    available, np.lexsort otherwise."""
    n, w = words.shape
    lib = _load()
    if lib is None or n == 0:
        if n == 0:
            return np.zeros(0, np.int32)
        return np.lexsort(tuple(words[:, i]
                                for i in range(w - 1, -1, -1))).astype(np.int32)
    words = np.ascontiguousarray(words, np.uint64)
    perm = np.empty(n, np.int32)
    lib.at_lex_sort_words(_as_u64p(words), n, w,
                          perm.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return perm


def merge_runs(words: np.ndarray, run_offsets: np.ndarray) -> np.ndarray:
    """Global merge order (row indices into `words`) for k sorted runs —
    run r occupies rows [run_offsets[r], run_offsets[r+1]). Loser tree in
    native code; numpy fallback concatenates and lex-sorts (stable, so run
    order breaks ties the same way)."""
    n, w = words.shape
    k = len(run_offsets) - 1
    lib = _load()
    if lib is None or n == 0:
        # a stable sort of the concatenation merges sorted runs with the
        # same run-order tie-break as the loser tree
        return lex_sort_words(words)
    words = np.ascontiguousarray(words, np.uint64)
    offsets = np.ascontiguousarray(run_offsets, np.int64)
    out = np.empty(n, np.int32)
    lib.at_merge_runs(_as_u64p(words),
                      offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                      k, w,
                      out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return out


def take_rows(src: np.ndarray, order: np.ndarray) -> np.ndarray:
    """out[i] = src[order[i]] over a row-major 2-D byte-like matrix."""
    lib = _load()
    if lib is None or src.size == 0:
        return src[order]
    src2 = np.ascontiguousarray(src)
    flat = src2.view(np.uint8).reshape(src2.shape[0], -1)
    order = np.ascontiguousarray(order, np.int32)
    out = np.empty((len(order), flat.shape[1]), np.uint8)
    lib.at_take_rows(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        order.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(order), flat.shape[1],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out.view(src2.dtype).reshape((len(order),) + src2.shape[1:])
