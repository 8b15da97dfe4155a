"""ctypes bindings for the native runtime library (libtpuserve_native.so).

The library is never committed: every load runs ``make``, which builds it
from the committed ``queue.cpp`` when the binary is missing or older than
its source and is a no-op otherwise — so what runs is always what the
checkout holds. Consumers fall back to their pure-Python path when it
cannot be built (no compiler, read-only filesystem) and say which one
serves (``FastSimpleQueue.backend``).
"""

from __future__ import annotations

import ctypes
import subprocess
from pathlib import Path
from typing import List, Optional

_NATIVE_DIR = Path(__file__).parent
_LIB_PATH = _NATIVE_DIR / "libtpuserve_native.so"
_lib = None
_lib_failed = False


def load_native() -> Optional[ctypes.CDLL]:
    """The shared library, (re)built from source if stale; None if
    unavailable."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    try:
        subprocess.run(
            ["make", "-s", "libtpuserve_native.so"],
            cwd=str(_NATIVE_DIR), check=True, capture_output=True, timeout=120,
        )
        lib = ctypes.CDLL(str(_LIB_PATH))
        lib.tpuserve_queue_create.restype = ctypes.c_void_p
        lib.tpuserve_queue_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
        lib.tpuserve_queue_destroy.argtypes = [ctypes.c_void_p]
        lib.tpuserve_queue_push.restype = ctypes.c_int
        lib.tpuserve_queue_push.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
        ]
        lib.tpuserve_queue_pop.restype = ctypes.c_int64
        lib.tpuserve_queue_pop.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.tpuserve_queue_size.restype = ctypes.c_uint64
        lib.tpuserve_queue_size.argtypes = [ctypes.c_void_p]
        lib.tpuserve_queue_dropped.restype = ctypes.c_uint64
        lib.tpuserve_queue_dropped.argtypes = [ctypes.c_void_p]
        lib.tpuserve_hist_create.restype = ctypes.c_void_p
        lib.tpuserve_hist_destroy.argtypes = [ctypes.c_void_p]
        lib.tpuserve_hist_observe.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.tpuserve_hist_snapshot.restype = ctypes.c_uint64
        lib.tpuserve_hist_snapshot.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.tpuserve_hist_num_buckets.restype = ctypes.c_int
        lib.tpuserve_hist_bounds.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.tpuserve_hist_total_us.restype = ctypes.c_uint64
        lib.tpuserve_hist_total_us.argtypes = [ctypes.c_void_p]
        _lib = lib
    except Exception:
        _lib_failed = True
        _lib = None
    return _lib


class NativeQueue:
    """Lock-free MPSC byte-message queue (raises RuntimeError if the native
    library is unavailable — callers pick the Python fallback instead)."""

    def __init__(self, capacity: int = 4096, cell_bytes: int = 4096):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._cell_bytes = cell_bytes
        self._q = lib.tpuserve_queue_create(capacity, cell_bytes)
        if not self._q:
            raise RuntimeError("native queue allocation failed")
        self._buf = ctypes.create_string_buffer(cell_bytes)

    def push(self, data: bytes) -> bool:
        return bool(self._lib.tpuserve_queue_push(self._q, data, len(data)))

    def pop(self) -> Optional[bytes]:
        n = self._lib.tpuserve_queue_pop(self._q, self._buf, self._cell_bytes)
        if n <= 0:
            return None
        return self._buf.raw[:n]

    def pop_all(self, limit: int = 100000) -> List[bytes]:
        out = []
        for _ in range(limit):
            item = self.pop()
            if item is None:
                break
            out.append(item)
        return out

    def __len__(self) -> int:
        return int(self._lib.tpuserve_queue_size(self._q))

    @property
    def rejected(self) -> int:
        """Count of pushes the ring refused (full/oversized). A rejected push
        is NOT necessarily a lost message — callers may retry or fall back."""
        return int(self._lib.tpuserve_queue_dropped(self._q))

    def __del__(self):
        try:
            if getattr(self, "_q", None):
                self._lib.tpuserve_queue_destroy(self._q)
                self._q = None
        except Exception:
            pass


class NativeHistogram:
    """Thread-safe microsecond latency histogram."""

    def __init__(self):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.tpuserve_hist_create()
        if not self._h:
            raise RuntimeError("native histogram allocation failed")
        self._n = int(lib.tpuserve_hist_num_buckets())

    def observe_seconds(self, seconds: float) -> None:
        self._lib.tpuserve_hist_observe(self._h, int(seconds * 1e6))

    def snapshot(self):
        counts = (ctypes.c_uint64 * self._n)()
        total = self._lib.tpuserve_hist_snapshot(self._h, counts)
        bounds = (ctypes.c_uint64 * (self._n - 1))()
        self._lib.tpuserve_hist_bounds(self._h, bounds)
        return {
            "total": int(total),
            "bounds_us": list(bounds),
            "counts": list(counts),
            "total_us": int(self._lib.tpuserve_hist_total_us(self._h)),
        }

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.tpuserve_hist_destroy(self._h)
                self._h = None
        except Exception:
            pass
