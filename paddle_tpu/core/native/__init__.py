"""Native runtime bindings (ctypes over paddle_tpu/core/native/native.cc).

The reference's native surface (layers 1-6 of SURVEY.md §1) collapses on TPU into
XLA/PJRT for everything device-side; what stays native is the host control plane and
IO: the TCPStore rendezvous server, the DataLoader prefetch ring, the chrome-trace
collector, and the pinned host staging pool.  This module compiles `native.cc` with
g++ on first use (cached in `_build/`), loads it with ctypes, and exposes typed
wrappers.  Every consumer has a pure-Python fallback, so a missing toolchain only
costs performance, never functionality (`AVAILABLE` tells you which path you're on).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")
_SRC = os.path.join(_HERE, "native.cc")
_LIB_PATH = os.path.join(_BUILD_DIR, "libpaddle_tpu_native.so")

_lib = None
_lib_lock = threading.Lock()
AVAILABLE = None  # resolved on first load_library() call
BUILT_THIS_RUN = False  # True once build() compiled native.cc in this process


def _needs_rebuild() -> bool:
    if not os.path.exists(_LIB_PATH):
        return True
    return os.path.getmtime(_SRC) > os.path.getmtime(_LIB_PATH)


def build(verbose: bool = False) -> str:
    """Compile native.cc -> libpaddle_tpu_native.so (cached by mtime)."""
    global BUILT_THIS_RUN
    os.makedirs(_BUILD_DIR, exist_ok=True)
    if not _needs_rebuild():
        return _LIB_PATH
    cmd = ["g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-pthread",
           _SRC, "-o", _LIB_PATH + ".tmp"]
    subprocess.run(cmd, check=True, capture_output=not verbose)
    os.replace(_LIB_PATH + ".tmp", _LIB_PATH)
    BUILT_THIS_RUN = True
    return _LIB_PATH


def load_library():
    """Load (building if needed).  Returns the CDLL or None if unavailable.

    Resolution order: env kill-switch -> fresh build (dev checkout with a
    toolchain) -> PREBUILT .so even if stale (wheel install on a
    compiler-less host) -> pure-Python fallbacks (AVAILABLE=False)."""
    global _lib, AVAILABLE
    if _lib is not None or AVAILABLE is False:
        return _lib
    with _lib_lock:
        if _lib is not None or AVAILABLE is False:
            return _lib
        if os.environ.get("PADDLE_TPU_DISABLE_NATIVE"):
            AVAILABLE = False
            return None
        # a lib loaded without a fresh compile THIS call may be a stale
        # artifact (copied build dir, docker layer with equal mtimes) — any
        # missing symbol then degrades instead of raising
        from_stale_prebuilt = not _needs_rebuild()
        try:
            path = build()
            lib = ctypes.CDLL(path)
        except Exception:
            # no toolchain: a prebuilt library (shipped in the wheel) still
            # loads — staleness only matters in dev checkouts, which have g++
            if os.path.exists(_LIB_PATH):
                try:
                    lib = ctypes.CDLL(_LIB_PATH)
                    from_stale_prebuilt = True
                except OSError:
                    AVAILABLE = False
                    return None
            else:
                AVAILABLE = False
                return None
        try:
            _declare(lib)
        except AttributeError:
            if not from_stale_prebuilt:
                raise  # fresh build missing a symbol IS a bug: fail loudly
            # a stale prebuilt .so missing newly-bound symbols: honor the
            # "CDLL or None" contract and degrade to pure Python
            AVAILABLE = False
            return None
        _lib = lib
        AVAILABLE = True
    return _lib


def _declare(lib):
    c = ctypes
    lib.pt_store_server_start.restype = c.c_void_p
    lib.pt_store_server_start.argtypes = [c.c_int]
    lib.pt_store_server_port.restype = c.c_int
    lib.pt_store_server_port.argtypes = [c.c_void_p]
    lib.pt_store_server_stop.argtypes = [c.c_void_p]

    lib.pt_ring_new.restype = c.c_void_p
    lib.pt_ring_new.argtypes = [c.c_int]
    lib.pt_ring_push.restype = c.c_int
    lib.pt_ring_push.argtypes = [c.c_void_p, c.c_char_p, c.c_int64, c.c_double]
    lib.pt_ring_pop.restype = c.c_int64
    lib.pt_ring_pop.argtypes = [c.c_void_p, c.c_char_p, c.c_int64, c.c_double]
    lib.pt_ring_peek_size.restype = c.c_int64
    lib.pt_ring_peek_size.argtypes = [c.c_void_p]
    lib.pt_ring_size.restype = c.c_int
    lib.pt_ring_size.argtypes = [c.c_void_p]
    lib.pt_ring_close.argtypes = [c.c_void_p]
    lib.pt_ring_free.argtypes = [c.c_void_p]

    lib.pt_trace_enable.argtypes = [c.c_int]
    lib.pt_trace_enabled.restype = c.c_int
    lib.pt_trace_begin.argtypes = [c.c_char_p]
    lib.pt_trace_complete.argtypes = [c.c_char_p, c.c_uint64, c.c_uint64]
    lib.pt_trace_count.restype = c.c_int64
    lib.pt_trace_dump_json.restype = c.c_int64
    lib.pt_trace_dump_json.argtypes = [c.c_char_p, c.c_int64]
    lib.pt_trace_now_us.restype = c.c_uint64

    lib.pt_pool_new.restype = c.c_void_p
    lib.pt_pool_alloc.restype = c.c_void_p
    lib.pt_pool_alloc.argtypes = [c.c_void_p, c.c_int64]
    lib.pt_pool_free.restype = c.c_int
    lib.pt_pool_free.argtypes = [c.c_void_p, c.c_void_p]
    lib.pt_pool_stats.argtypes = [c.c_void_p, c.POINTER(c.c_int64 * 5)]
    lib.pt_pool_trim.argtypes = [c.c_void_p]
    lib.pt_pool_delete.argtypes = [c.c_void_p]

    lib.pt_native_abi_version.restype = c.c_int


# ------------------------------------------------------------------ wrappers
class NativeKVServer:
    """C++ TCPStore server (same wire protocol as distributed.store.TCPStore,
    so Python clients talk to it unchanged)."""

    def __init__(self, port: int = 0):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.pt_store_server_start(port)
        if not self._h:
            raise OSError(f"failed to bind KV server on port {port}")
        self.port = lib.pt_store_server_port(self._h)

    def stop(self):
        if self._h:
            self._lib.pt_store_server_stop(self._h)
            self._h = None

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


class NativeRing:
    """GIL-free bounded byte queue for DataLoader prefetch."""

    def __init__(self, capacity: int = 8):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.pt_ring_new(capacity)

    def push(self, data: bytes, timeout: float = -1.0) -> bool:
        if self._h is None:
            return False
        rc = self._lib.pt_ring_push(self._h, data, len(data), timeout)
        if rc == -1:
            raise TimeoutError("ring push timed out")
        return rc == 1

    def pop(self, timeout: float = -1.0) -> bytes | None:
        while True:
            if self._h is None:
                return None
            size = self._lib.pt_ring_peek_size(self._h)
            cap = max(size, 1 << 16)
            buf = ctypes.create_string_buffer(cap)
            n = self._lib.pt_ring_pop(self._h, buf, cap, timeout)
            if n == -1:
                raise TimeoutError("ring pop timed out")
            if n == -2:
                continue  # raced with a larger item; retry with its size
            if n == -3:
                return b""  # popped item with empty payload (distinct from end)
            if n == 0:
                return None  # closed and drained
            return buf.raw[:n]

    def qsize(self) -> int:
        return self._lib.pt_ring_size(self._h) if self._h is not None else 0

    def close(self):
        if self._h is not None:
            self._lib.pt_ring_close(self._h)

    def free(self):
        if self._h:
            self._lib.pt_ring_free(self._h)
            self._h = None


class NativeTracer:
    """Span collector; dump() returns chrome://tracing JSON."""

    def __init__(self):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib

    def enable(self, on: bool = True):
        self._lib.pt_trace_enable(1 if on else 0)

    def now_us(self) -> int:
        return self._lib.pt_trace_now_us()

    def complete(self, name: str, ts_us: int, dur_us: int):
        self._lib.pt_trace_complete(name.encode(), ts_us, dur_us)

    def count(self) -> int:
        return self._lib.pt_trace_count()

    def clear(self):
        self._lib.pt_trace_clear()

    def dump_json(self) -> str:
        need = self._lib.pt_trace_dump_json(None, 0)
        buf = ctypes.create_string_buffer(need + 1)
        self._lib.pt_trace_dump_json(buf, need)
        return buf.raw[:need].decode()


class NativePool:
    """Host staging-buffer pool with stats (allocated, in_use, peak, hits, misses)."""

    def __init__(self):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.pt_pool_new()

    def alloc(self, n: int) -> int:
        ptr = self._lib.pt_pool_alloc(self._h, n)
        if not ptr:
            raise MemoryError(f"pool alloc of {n} bytes failed")
        return ptr

    def free(self, ptr: int):
        if self._lib.pt_pool_free(self._h, ptr) != 0:
            raise ValueError("pointer not allocated from this pool")

    def stats(self) -> dict:
        arr = (ctypes.c_int64 * 5)()
        self._lib.pt_pool_stats(self._h, ctypes.byref(arr))
        return {"allocated": arr[0], "in_use": arr[1], "peak": arr[2],
                "hits": arr[3], "misses": arr[4]}

    def trim(self):
        self._lib.pt_pool_trim(self._h)

    def delete(self):
        if self._h:
            self._lib.pt_pool_delete(self._h)
            self._h = None
