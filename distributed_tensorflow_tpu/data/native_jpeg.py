"""Native libjpeg decode stage (ctypes over native/dtf_jpeg.cpp).

The JPEG input path's hot loop — header parse, DCT-domain downscaled
decode, crop, bilinear resize — in C++ with a thread pool, plugged under
``JpegClassificationDataset`` (``decoder="native"``). The crop POLICY
(which rect, which flips) stays in Python (augment.sample_crop_rect), so
the augmentation recipe has exactly one definition; this stage only
executes pixels. The native tier serves the flagship JPEG path, not
just the dense-record loader.

Build policy is runtime/native.py's: compile on first use (g++ -O3,
links -ljpeg) into a library keyed by a hash of the source, degrade to
the PIL path when the toolchain or libjpeg is missing.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

from ..runtime.native import build_library

logger = logging.getLogger(__name__)

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO, "native", "dtf_jpeg.cpp")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.dtf_jpeg_dims.restype = c.c_int
    lib.dtf_jpeg_dims.argtypes = [
        c.POINTER(c.c_uint8), c.POINTER(c.c_int64), c.POINTER(c.c_int64),
        c.c_int64, c.POINTER(c.c_int64),
    ]
    lib.dtf_jpeg_decode_crop_resize.restype = c.c_int
    lib.dtf_jpeg_decode_crop_resize.argtypes = [
        c.POINTER(c.c_uint8), c.POINTER(c.c_int64), c.POINTER(c.c_int64),
        c.POINTER(c.c_int64), c.c_int64, c.c_int,
        c.POINTER(c.c_uint8), c.c_int,
    ]
    return lib


def load_library() -> ctypes.CDLL | None:
    """Build (once) and load libdtf_jpeg.so; None when unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            _lib = _configure(ctypes.CDLL(
                build_library(_SRC, "dtf_jpeg", link=("-ljpeg",))))
        except (OSError, subprocess.SubprocessError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            logger.info("native jpeg decoder unavailable (%s); "
                        "using the PIL path", detail.strip()[:200])
            _lib = None
        return _lib


def available() -> bool:
    return load_library() is not None


def _i64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _bounded(data: np.ndarray, offsets: np.ndarray,
             lengths: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Clamp (offset, length) pairs to the data buffer: a corrupt index
    entry must become a catchable short-stream decode failure (zero-fill
    contract), never an out-of-bounds read in C."""
    off = np.clip(np.ascontiguousarray(offsets, np.int64), 0, data.size)
    ln = np.clip(np.ascontiguousarray(lengths, np.int64), 0,
                 data.size - off)
    return off, ln


def jpeg_dims(data: np.ndarray, offsets: np.ndarray,
              lengths: np.ndarray) -> np.ndarray:
    """[N, 2] (h, w) per stream; zeros for unparsable streams."""
    lib = load_library()
    n = len(offsets)
    dims = np.zeros((n, 2), np.int64)
    off, ln = _bounded(data, offsets, lengths)
    lib.dtf_jpeg_dims(_u8p(data), _i64p(off), _i64p(ln), n, _i64p(dims))
    return dims


def decode_crop_resize(data: np.ndarray, offsets: np.ndarray,
                       lengths: np.ndarray, rects: np.ndarray,
                       out_size: int, n_threads: int) -> np.ndarray:
    """Decode N streams, crop rect (y, x, ch, cw in full-res coords),
    bilinear-resize to [N, out_size, out_size, 3] u8. Failed streams come
    back zeroed (the caller's record file is validated at conversion
    time; a zero image in a training batch is noise, not a crash)."""
    lib = load_library()
    n = len(offsets)
    out = np.empty((n, out_size, out_size, 3), np.uint8)
    off, ln = _bounded(data, offsets, lengths)
    rc = np.ascontiguousarray(rects, np.int64)
    bad = lib.dtf_jpeg_decode_crop_resize(
        _u8p(data), _i64p(off), _i64p(ln), _i64p(rc), n, out_size,
        _u8p(out), n_threads,
    )
    if bad:
        logger.warning("native jpeg decode: %d/%d streams failed "
                       "(zero-filled)", bad, n)
    return out
