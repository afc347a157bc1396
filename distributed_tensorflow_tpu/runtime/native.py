"""Build + load the native runtime library (ctypes).

Policy: compile on first use with g++ (-O3, no external deps), keep the
.so under native/build/ keyed by a hash of its source (a copied or
checked-out tree's mtimes say nothing, so a stale binary is never
loaded), degrade with a warning to the Python fallbacks if a toolchain
isn't present. The C ABI is small and stable — see native/dtf_runtime.cpp
for the contract.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

logger = logging.getLogger(__name__)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO, "native", "dtf_runtime.cpp")
_BUILD_DIR = os.path.join(_REPO, "native", "build")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.dtf_loader_create.restype = c.c_void_p
    lib.dtf_loader_create.argtypes = [
        c.c_char_p, c.c_int64, c.c_int64, c.c_int, c.c_int, c.c_uint64,
        c.c_int64, c.c_int64, c.c_int64,
    ]
    lib.dtf_loader_batches_per_epoch.restype = c.c_int64
    lib.dtf_loader_batches_per_epoch.argtypes = [c.c_void_p]
    lib.dtf_loader_n_records.restype = c.c_int64
    lib.dtf_loader_n_records.argtypes = [c.c_void_p]
    lib.dtf_loader_next.restype = c.c_void_p
    lib.dtf_loader_next.argtypes = [c.c_void_p]
    lib.dtf_batch_data.restype = c.POINTER(c.c_uint8)
    lib.dtf_batch_data.argtypes = [c.c_void_p]
    lib.dtf_batch_index.restype = c.c_int64
    lib.dtf_batch_index.argtypes = [c.c_void_p]
    lib.dtf_loader_release.argtypes = [c.c_void_p, c.c_void_p]
    lib.dtf_loader_destroy.argtypes = [c.c_void_p]
    lib.dtf_loader_batch_indices.argtypes = [
        c.c_void_p, c.c_int64, c.POINTER(c.c_int64),
    ]
    lib.dtf_epoch_permutation.argtypes = [
        c.c_int64, c.c_uint64, c.POINTER(c.c_int64),
    ]
    lib.dtf_write_file.restype = c.c_int
    lib.dtf_write_file.argtypes = [c.c_char_p, c.c_void_p, c.c_int64]
    lib.dtf_read_file.restype = c.c_int64
    lib.dtf_read_file.argtypes = [c.c_char_p, c.c_void_p, c.c_int64]
    lib.dtf_crc32.restype = c.c_uint32
    lib.dtf_crc32.argtypes = [c.c_void_p, c.c_int64]
    return lib


def library_path(src: str, name: str) -> str:
    """``native/build/lib<name>-<hash of the source>.so``: changing the
    source changes the path, so a binary built from other source is
    never picked up."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"lib{name}-{digest}.so")


def build_library(src: str, name: str, link: tuple[str, ...] = ()) -> str:
    """Path of the shared library built from ``src``, compiling it with
    g++ unless this exact source was built before. Raises OSError /
    subprocess.SubprocessError when the source or toolchain is missing
    (callers fall back to their Python tier)."""
    so = library_path(src, name)
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # per-process tmp name: concurrent first-use builds (multi-process jax,
    # pytest-xdist) each write their own file; os.replace is atomic, last
    # writer wins with a complete library either way
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
             src, "-o", tmp, *link],
            check=True, capture_output=True, text=True, timeout=180)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so


def load_library() -> ctypes.CDLL | None:
    """The loaded native library, building it if needed; None if
    unavailable (callers must fall back)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            so = build_library(_SRC, "dtf_runtime")
        except (OSError, subprocess.SubprocessError) as e:
            logger.warning("native runtime build failed (%s); using Python "
                           "fallbacks", e)
            return None
        try:
            lib = _configure(ctypes.CDLL(so))
            # sanity-probe a pure function; a corrupt .so fails here, and
            # deleting it makes the next process rebuild cleanly
            if lib.dtf_crc32(b"123456789", 9) != 0xCBF43926:
                raise OSError("crc self-test failed")
            _lib = lib
        except (OSError, AttributeError) as e:
            logger.warning("native runtime load failed (%s); rebuilding "
                           "next run", e)
            try:
                os.unlink(so)
            except OSError:
                pass
            _lib = None
        return _lib


def available() -> bool:
    return load_library() is not None
