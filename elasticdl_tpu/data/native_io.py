"""ctypes bindings for the native TFRecord scanner (native/recordio.cc).

The shared library is built by `make -C native` (or scripts/build_native.sh)
— attempted automatically once per process if g++ is available.  All
callers degrade to the pure-Python implementation when the library is
missing — an order of magnitude slower, so the fallback is logged once
per process at ERROR and `available()` lets a caller that must not run
degraded (chip_smoke.py) refuse.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional, Tuple

import numpy as np

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger(__name__)

_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SO_PATH = os.path.join(_ROOT, "native", "build", "librecordio.so")

_lib = None
_build_attempted = False
_fallback_logged = False


def _log_fallback(reason: str) -> None:
    global _fallback_logged
    if not _fallback_logged:
        _fallback_logged = True
        logger.error(
            "native record scanner unavailable (%s): falling back to the "
            "pure-Python TFRecord path, which is far slower — run "
            "scripts/build_native.sh to see the build error", reason,
        )


def _try_build() -> None:
    """Build the .so (at most once per process).  Cross-process safe
    (ADVICE r4): the Makefile compiles to a temp name and atomically
    renames, so a concurrent reader never dlopens a half-written file,
    and an flock on a sidecar lockfile serializes concurrent makes so N
    workers starting together run one compile, not N."""
    global _build_attempted
    if _build_attempted:
        return
    _build_attempted = True
    native_dir = os.path.join(_ROOT, "native")
    if not os.path.exists(os.path.join(native_dir, "Makefile")):
        return
    try:
        import fcntl
    except ImportError:
        fcntl = None          # non-POSIX: build unlocked (still atomic)
    try:
        os.makedirs(os.path.join(native_dir, "build"), exist_ok=True)
        with open(os.path.join(native_dir, "build", ".lock"), "w") as lock:
            if fcntl is not None:
                fcntl.flock(lock, fcntl.LOCK_EX)
            try:
                subprocess.run(
                    ["make", "-C", native_dir],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
            finally:
                if fcntl is not None:
                    fcntl.flock(lock, fcntl.LOCK_UN)
    except (subprocess.SubprocessError, OSError) as exc:
        stderr = getattr(exc, "stderr", b"") or b""
        _log_fallback(
            f"build failed: {exc!r} "
            f"{stderr.decode(errors='replace')[-500:]}"
        )


def _stale() -> bool:
    source = os.path.join(_ROOT, "native", "recordio.cc")
    try:
        return os.path.getmtime(_SO_PATH) < os.path.getmtime(source)
    except OSError:
        return True


def _load():
    global _lib
    if _lib is not None:
        return _lib
    if not os.path.exists(_SO_PATH) or _stale():
        _try_build()
    if not os.path.exists(_SO_PATH):
        _log_fallback(f"{_SO_PATH} was not built")
        return None
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError as exc:
        # corrupt artifact (e.g. from an interrupted historical build):
        # degrade to the pure-Python path rather than crash the worker
        _log_fallback(f"dlopen failed: {exc}")
        return None
    lib.recordio_build_index.restype = ctypes.c_int64
    lib.recordio_build_index.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
    ]
    lib.recordio_read_records.restype = ctypes.c_int64
    lib.recordio_read_records.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
    ]
    lib.recordio_free.restype = None
    lib.recordio_free.argtypes = [ctypes.c_void_p]
    if hasattr(lib, "recordio_write_records"):
        lib.recordio_write_records.restype = ctypes.c_int64
        lib.recordio_write_records.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.c_int,
        ]
    _lib = lib
    return lib


def can_write() -> bool:
    lib = _load()
    return lib is not None and hasattr(lib, "recordio_write_records")


def write_records(
    path: str, buffer: np.ndarray, sizes: np.ndarray, append: bool = False
) -> int:
    """Write n records (contiguous uint8 payloads + int64 sizes) with
    TFRecord framing, CRCs computed in C.  Returns bytes written."""
    lib = _load()
    assert lib is not None and hasattr(lib, "recordio_write_records")
    buffer = np.ascontiguousarray(buffer, np.uint8)
    sizes = np.ascontiguousarray(sizes, np.int64)
    rc = lib.recordio_write_records(
        path.encode(),
        buffer.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(sizes),
        int(append),
    )
    if rc < 0:
        raise IOError(f"native record write failed for {path} (rc={rc})")
    return rc


def available() -> bool:
    return _load() is not None


def build_index(path: str) -> np.ndarray:
    lib = _load()
    assert lib is not None
    out = ctypes.POINTER(ctypes.c_int64)()
    n = lib.recordio_build_index(path.encode(), ctypes.byref(out))
    if n < 0:
        raise IOError(f"native index build failed for {path} (rc={n})")
    try:
        if n == 0:
            return np.empty(0, np.int64)
        return np.ctypeslib.as_array(out, shape=(n,)).copy()
    finally:
        lib.recordio_free(out)


def read_records_np(
    path: str, offsets: List[int], start: int, end: int,
    check_crc: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """Bulk read: one (uint8 payload buffer, int64 sizes) pair for records
    [start, end) — the scanner's contiguous output handed to Python as
    numpy arrays with NO per-record splitting.  This is the zero-copy-ish
    fast path `feed_bulk` consumers (vectorized record parsing) ride."""
    lib = _load()
    assert lib is not None
    end = min(end, len(offsets))
    if start >= end:
        return np.empty(0, np.uint8), np.empty(0, np.int64)
    # offsets ride as a numpy int64 pointer: building a ctypes array from
    # a Python list converts every element (measured 8.6s for a 2M-record
    # index — dwarfing the read itself)
    arr = np.ascontiguousarray(offsets, np.int64)
    data = ctypes.POINTER(ctypes.c_uint8)()
    sizes = ctypes.POINTER(ctypes.c_int64)()
    total = lib.recordio_read_records(
        path.encode(),
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        start, end, int(check_crc),
        ctypes.byref(data), ctypes.byref(sizes),
    )
    if total < 0:
        raise IOError(f"native record read failed for {path} (rc={total})")
    try:
        # one memcpy each out of the C buffers, then free them
        buf = np.ctypeslib.as_array(data, shape=(total,)).copy()
        size_arr = np.ctypeslib.as_array(
            sizes, shape=(end - start,)
        ).copy()
        return buf, size_arr
    finally:
        lib.recordio_free(data)
        lib.recordio_free(sizes)


def read_records(
    path: str, offsets: List[int], start: int, end: int,
    check_crc: bool = False,
) -> Optional[List[bytes]]:
    buf, sizes = read_records_np(path, offsets, start, end, check_crc)
    blob = buf.tobytes()
    result = []
    pos = 0
    for size in sizes:
        result.append(blob[pos : pos + size])
        pos += size
    return result
