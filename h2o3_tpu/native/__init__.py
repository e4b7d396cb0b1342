"""ctypes bindings for the native (C++) runtime components.

Reference mapping (SURVEY.md §2.3: native components get TPU-native
equivalents, and the runtime around the JAX compute path is native):

  * ``native/csv.cpp``    — the parser hot loop (water/parser/CsvParser.java
    byte scanning, chunk-parallel like MultiFileParseTask)
  * ``native/codecs.cpp`` — chunk compression codecs (water/fvec/C*Chunk)
    + LSD radix argsort (water/rapids/RadixOrder.java analogue)

The library is built on first use from ``native/csv.cpp``,
``native/codecs.cpp`` and ``native/Makefile`` — nothing else, and the ``.so``
is git-ignored, so a clean checkout always builds. If the build fails (no
compiler) callers use the numpy fallbacks, and the failure is logged once at
WARNING with the compiler's output; ``available()`` says which one a process
got. H2O3_TPU_NATIVE=0 selects the fallbacks on purpose.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from h2o3_tpu.util.log import get_logger

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_LIB_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "libh2o3native.so"))

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    """``make`` the library. ``get_lib`` tries once per process, under
    ``_lock``, so a failure is logged once."""
    try:
        out = subprocess.run(
            ["make", "-C", os.path.abspath(_NATIVE_DIR)],
            capture_output=True, text=True, timeout=120,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        get_logger("native").warning(
            "native build did not run (%s: %s); using the numpy fallbacks",
            type(e).__name__, e)
        return False
    if out.returncode != 0 or not os.path.exists(_LIB_PATH):
        get_logger("native").warning(
            "native build failed (make exit %d); using the numpy "
            "fallbacks:\n%s", out.returncode, (out.stderr or out.stdout)[-4000:])
        return False
    return True


def _stale() -> bool:
    """True when a source file is newer than the built library (the .so
    would lack symbols added since it was compiled)."""
    try:
        lib_m = os.path.getmtime(_LIB_PATH)
        return any(
            os.path.getmtime(os.path.join(_NATIVE_DIR, f)) > lib_m
            for f in ("csv.cpp", "codecs.cpp")
        )
    except OSError:
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building it on first use; None if unavailable."""
    global _lib, _tried
    if _lib is not None:
        return _lib
    if os.environ.get("H2O3_TPU_NATIVE", "1") == "0":
        return None
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_LIB_PATH) and not _build():
            return None
        if _stale() and not _build():
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            get_logger("native").warning(
                "native library %s did not load (%s); using the numpy "
                "fallbacks", _LIB_PATH, e)
            return None
        lib.h2o3_count_rows.restype = ctypes.c_int64
        lib.h2o3_count_rows.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.h2o3_parse_numeric_csv.restype = ctypes.c_int64
        lib.h2o3_parse_numeric_csv.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_char,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
            ctypes.c_int32,
        ]
        _i32p = ctypes.POINTER(ctypes.c_int32)
        _u8p = ctypes.POINTER(ctypes.c_uint8)
        _f64p = ctypes.POINTER(ctypes.c_double)
        lib.h2o3_csv_index_chunk.restype = ctypes.c_int64
        lib.h2o3_csv_index_chunk.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char, ctypes.c_int32,
            ctypes.c_int32, _i32p, _i32p, ctypes.c_int64,
        ]
        lib.h2o3_parse_cells_f64.restype = None
        lib.h2o3_parse_cells_f64.argtypes = [
            ctypes.c_char_p, _i32p, _i32p, ctypes.c_int64, _f64p,
        ]
        lib.h2o3_parse_cells_time.restype = ctypes.c_int64
        lib.h2o3_parse_cells_time.argtypes = [
            ctypes.c_char_p, _i32p, _i32p, ctypes.c_int64, _f64p, _u8p,
        ]
        lib.h2o3_dict_encode_cells.restype = ctypes.c_int64
        lib.h2o3_dict_encode_cells.argtypes = [
            ctypes.c_char_p, _i32p, _i32p, ctypes.c_int64,
            ctypes.c_char_p, _i32p, _i32p, ctypes.c_int32,
            _i32p, _i32p, _i32p,
        ]
        lib.h2o3_gather_cells.restype = ctypes.c_int64
        lib.h2o3_gather_cells.argtypes = [
            ctypes.c_char_p, _i32p, _i32p, ctypes.c_int64,
            ctypes.c_char_p, _i32p, _i32p, ctypes.c_int32,
            ctypes.c_char_p, _u8p,
        ]
        lib.h2o3_codec_bound.restype = ctypes.c_int64
        lib.h2o3_codec_bound.argtypes = [ctypes.c_int64]
        lib.h2o3_codec_encode.restype = ctypes.c_int64
        lib.h2o3_codec_encode.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.h2o3_codec_decode.restype = ctypes.c_int64
        lib.h2o3_codec_decode.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_double),
        ]
        lib.h2o3_radix_argsort_u64.restype = None
        lib.h2o3_radix_argsort_u64.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


# ---------------------------------------------------------------------------
# csv


def parse_numeric_csv(
    text: bytes, start: int, sep: str, ncols: int, nrows: int,
    nthreads: int = 0,
) -> Optional[np.ndarray]:
    """All-numeric CSV body -> [nrows, ncols] float64 (NaN = NA/junk).
    Returns None when the native lib is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    if nthreads <= 0:
        nthreads = min(os.cpu_count() or 1, 8)
    out = np.empty((nrows, ncols), dtype=np.float64)
    got = lib.h2o3_parse_numeric_csv(
        text, len(text), start, sep.encode()[:1], ncols,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), nrows, nthreads,
    )
    if got < 0 or got > nrows:
        return None
    return out[:got]


# ---------------------------------------------------------------------------
# chunk-parallel two-phase parse primitives (frame/parse.py workers)
#
# Every wrapper is one ctypes call over one body chunk; ctypes drops the
# GIL for the call's duration, which is what lets the ThreadPoolExecutor
# in frame/parse.py tokenize chunks genuinely concurrently.


def _i32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _u8(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _f64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


def csv_index_chunk(
    chunk: bytes, sep: str, ncols: int, skip_blanks: bool
) -> Optional[tuple]:
    """Tokenize one body chunk -> ([n, ncols] cell starts, ends) offset
    grids (whitespace-stripped; blank records skipped). None if the lib is
    unavailable or the preallocation was insufficient."""
    lib = get_lib()
    if lib is None:
        return None
    cap = chunk.count(b"\n") + 1
    starts = np.empty(cap * ncols, dtype=np.int32)
    ends = np.empty(cap * ncols, dtype=np.int32)
    n = lib.h2o3_csv_index_chunk(
        chunk, len(chunk), sep.encode()[:1], ncols,
        1 if skip_blanks else 0, _i32(starts), _i32(ends), cap,
    )
    if n < 0:
        return None
    return (
        starts[: n * ncols].reshape(n, ncols),
        ends[: n * ncols].reshape(n, ncols),
    )


def parse_cells_f64(
    chunk: bytes, starts: np.ndarray, ends: np.ndarray
) -> Optional[np.ndarray]:
    """One column's cells -> float64 (NaN for NA/junk)."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(starts)
    out = np.empty(n, dtype=np.float64)
    lib.h2o3_parse_cells_f64(chunk, _i32(starts), _i32(ends), n, _f64(out))
    return out


def parse_cells_time(
    chunk: bytes, starts: np.ndarray, ends: np.ndarray
) -> Optional[tuple]:
    """One column's cells -> epoch-ms float64 for strictly canonical time
    tokens, plus a uint8 flag array marking cells the caller must re-parse
    in python (NA tokens / nonstandard formats)."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(starts)
    out = np.empty(n, dtype=np.float64)
    flags = np.empty(n, dtype=np.uint8)
    lib.h2o3_parse_cells_time(
        chunk, _i32(starts), _i32(ends), n, _f64(out), _u8(flags)
    )
    return out, flags


def dict_encode_cells(
    chunk: bytes, starts: np.ndarray, ends: np.ndarray,
    na_blob: bytes, na_starts: np.ndarray, na_ends: np.ndarray,
) -> Optional[tuple]:
    """One column's cells -> (int32 codes, uniq_starts, uniq_ends): the
    local categorical dictionary in first-appearance order as offsets into
    the chunk; NA cells get code -1."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(starts)
    codes = np.empty(n, dtype=np.int32)
    ust = np.empty(n, dtype=np.int32)
    uen = np.empty(n, dtype=np.int32)
    nu = lib.h2o3_dict_encode_cells(
        chunk, _i32(starts), _i32(ends), n,
        na_blob, _i32(na_starts), _i32(na_ends), len(na_starts),
        _i32(codes), _i32(ust), _i32(uen),
    )
    return codes, ust[:nu], uen[:nu]


def gather_cells(
    chunk: bytes, starts: np.ndarray, ends: np.ndarray,
    na_blob: bytes, na_starts: np.ndarray, na_ends: np.ndarray,
) -> Optional[tuple]:
    """One column's cells -> (newline-joined bytes, uint8 NA mask), for a
    single bulk decode+split on the python side."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(starts)
    total = int((ends.astype(np.int64) - starts).sum()) + n
    out = ctypes.create_string_buffer(max(total, 1))
    mask = np.empty(n, dtype=np.uint8)
    got = lib.h2o3_gather_cells(
        chunk, _i32(starts), _i32(ends), n,
        na_blob, _i32(na_starts), _i32(na_ends), len(na_starts),
        out, _u8(mask),
    )
    return out.raw[:got], mask


# ---------------------------------------------------------------------------
# chunk codecs (compressed column store)


def codec_encode(x: np.ndarray) -> Optional[bytes]:
    lib = get_lib()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.float64)
    buf = np.empty(int(lib.h2o3_codec_bound(len(x))), dtype=np.uint8)
    n = lib.h2o3_codec_encode(
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), len(x),
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return bytes(buf[:n])


def codec_decode(blob: bytes) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    n = int.from_bytes(blob[1:9], "little")
    out = np.empty(n, dtype=np.float64)
    raw = np.frombuffer(blob, dtype=np.uint8)
    got = lib.h2o3_codec_decode(
        raw.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
    )
    if got != n:
        return None
    return out


# ---------------------------------------------------------------------------
# radix argsort


def radix_argsort(keys: np.ndarray) -> Optional[np.ndarray]:
    """Stable LSD-radix argsort for int64/uint64/float64 keys (NaN last)."""
    lib = get_lib()
    if lib is None:
        return None
    k = np.asarray(keys)
    if k.dtype == np.float64:
        # order-preserving float->uint64 transform (flip sign bit / negate);
        # canonicalize NaNs (negative-sign NaNs must also sort last) and
        # -0.0 -> +0.0 (numpy treats them as equal ties; the bit transform
        # would otherwise order them)
        k = np.where(np.isnan(k), np.nan, k + 0.0)
        bits = k.view(np.uint64).copy()
        neg = bits >> np.uint64(63) == 1
        bits[neg] = ~bits[neg]
        bits[~neg] |= np.uint64(1) << np.uint64(63)
        # NaNs (exponent all-ones, mantissa != 0) end up above +inf: fine
        u = bits
    elif k.dtype == np.int64:
        u = (k.astype(np.int64) ^ np.int64(-0x8000000000000000)).view(np.uint64)
    elif k.dtype == np.uint64:
        u = k
    else:
        return None
    u = np.ascontiguousarray(u)
    order = np.empty(len(u), dtype=np.int64)
    lib.h2o3_radix_argsort_u64(
        u.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), len(u),
        order.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return order
