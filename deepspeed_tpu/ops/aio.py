"""Python binding for the dstpu_aio C++ async file-I/O library.

Parity: reference ``ops/aio`` / ``csrc/aio/py_ds_aio.cpp`` ``aio_handle``
(``async_pread``/``async_pwrite``/``wait``) and the op-builder JIT-compile flow
(``op_builder/builder.py:545 jit_load``) — here the "builder" is one g++
invocation, cached next to the package (no torch cpp_extension machinery).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Dict, Optional

import numpy as np

from deepspeed_tpu.analysis.racelint.sanitizer import make_lock

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
_SRC = os.path.join(_REPO_ROOT, "csrc", "aio", "aio.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "build")

_lib = None
_lib_lock = make_lock("aio._lib_lock")


def _so_path() -> str:
    """The built library is keyed by a hash of its source: in a copied or
    freshly checked-out tree mtimes mean nothing, and ``build/`` is not
    tracked, so what runs is always compiled from the tracked ``.cpp``."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libdstpu_aio.{digest}.so")


def _build_library(force: bool = False) -> str:
    so_path = _so_path()
    if force or not os.path.exists(so_path):
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # compile beside the target and rename: another process loading
        # the library must never see a half-written file
        tmp = f"{so_path}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
                 _SRC, "-o", tmp], check=True, capture_output=True)
            os.replace(tmp, so_path)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
    return so_path


def _load() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is None:
            # build-once REQUIRES holding the lock across the compile:
            # two threads racing g++ on the same .so is the bug this
            # lock exists to prevent, hence the racelint suppressions
            try:
                lib = ctypes.CDLL(_build_library())   # racelint: disable=lock-across-blocking
            except OSError:
                # a cached .so built on another image (libstdc++/GLIBCXX
                # mismatch) matches the source hash but fails to load —
                # rebuild for THIS toolchain and retry
                lib = ctypes.CDLL(_build_library(force=True))   # racelint: disable=lock-across-blocking
            lib.aio_handle_create.restype = ctypes.c_void_p
            lib.aio_handle_create.argtypes = [ctypes.c_int]
            lib.aio_handle_destroy.argtypes = [ctypes.c_void_p]
            lib.aio_submit_pwrite.restype = ctypes.c_int
            lib.aio_submit_pwrite.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
                ctypes.c_long, ctypes.c_long]
            lib.aio_submit_pread.restype = ctypes.c_int
            lib.aio_submit_pread.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
                ctypes.c_long, ctypes.c_long]
            lib.aio_wait.restype = ctypes.c_long
            lib.aio_wait.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.aio_wait_all.restype = ctypes.c_int
            lib.aio_wait_all.argtypes = [ctypes.c_void_p]
            lib.aio_pending.restype = ctypes.c_int
            lib.aio_pending.argtypes = [ctypes.c_void_p]
            lib.aio_handle_create_ex.restype = ctypes.c_void_p
            lib.aio_handle_create_ex.argtypes = [
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_long,
                ctypes.c_int]
            lib.aio_uring_supported.restype = ctypes.c_int
            lib.aio_uring_supported.argtypes = []
            _lib = lib
    return _lib


def uring_supported() -> bool:
    """True when the kernel accepts io_uring_setup (DeepNVMe fast path)."""
    try:
        return bool(_load().aio_uring_supported())
    except Exception as e:   # no compiler / load failure -> threads engine
        from deepspeed_tpu.utils.logging import logger

        logger.debug(f"io_uring probe failed ({type(e).__name__}: {e}); "
                     "falling back to the thread-pool engine")
        return False


class AsyncIOHandle:
    """The reference ``aio_handle`` analog over numpy buffers.

    Buffers passed to async ops MUST stay alive until wait(); the handle keeps
    a reference until the op is waited on to enforce that."""

    def __init__(self, n_threads: int = 4, engine: str = "auto",
                 odirect: bool = False, block_bytes: int = 1 << 20,
                 queue_depth: int = 32):
        """``engine``: 'threads' (pread/pwrite pool), 'uring' (raw io_uring
        chunked submission — the reference's libaio/io_uring engines), or
        'auto' (uring when the kernel supports it; DSTPU_AIO_ENGINE env
        overrides). ``odirect``/``block_bytes``/``queue_depth`` mirror the
        reference aio config (block_size / queue_depth / overlap knobs)."""
        self._lib = _load()
        if engine == "auto":
            # the env override applies ONLY to auto — an explicit engine
            # argument (tuning sweeps, tests) is always honored
            engine = os.environ.get("DSTPU_AIO_ENGINE", "auto")
        if engine == "auto":
            engine = "uring" if self._lib.aio_uring_supported() else "threads"
        if engine not in ("threads", "uring"):
            raise ValueError(f"engine must be auto|threads|uring, got {engine!r}")
        self.engine = engine
        self._h = self._lib.aio_handle_create_ex(
            n_threads, 1 if engine == "uring" else 0, int(odirect),
            block_bytes, queue_depth)
        self._live: Dict[int, np.ndarray] = {}

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.aio_wait_all(self._h)
                self._lib.aio_handle_destroy(self._h)
                self._h = None
        # interpreter teardown: ctypes globals / the lib itself may already
        # be gone, and raising from __del__ only prints noise
        except Exception:   # dslint: disable=silent-except
            pass

    # ------------------------------------------------------------ #
    def async_pwrite(self, buf: np.ndarray, path: str, offset: int = 0) -> int:
        buf = np.ascontiguousarray(buf)
        op = self._lib.aio_submit_pwrite(
            self._h, path.encode(), buf.ctypes.data_as(ctypes.c_void_p),
            buf.nbytes, offset)
        if op < 0:
            raise OSError(-op, os.strerror(-op), path)
        self._live[op] = buf
        return op

    def async_pread(self, buf: np.ndarray, path: str, offset: int = 0) -> int:
        if not buf.flags["C_CONTIGUOUS"] or not buf.flags["WRITEABLE"]:
            raise ValueError("pread buffer must be contiguous and writeable")
        op = self._lib.aio_submit_pread(
            self._h, path.encode(), buf.ctypes.data_as(ctypes.c_void_p),
            buf.nbytes, offset)
        if op < 0:
            raise OSError(-op, os.strerror(-op), path)
        self._live[op] = buf
        return op

    def wait(self, op_id: int) -> int:
        rc = self._lib.aio_wait(self._h, op_id)
        self._live.pop(op_id, None)
        if rc < 0:
            raise OSError(-rc, os.strerror(-rc))
        return int(rc)

    def wait_all(self) -> None:
        rc = self._lib.aio_wait_all(self._h)
        self._live.clear()
        if rc < 0:
            raise OSError(-rc, os.strerror(-rc))

    def pending(self) -> int:
        return int(self._lib.aio_pending(self._h))

    # sync convenience (reference sync_pread/sync_pwrite)
    def sync_pwrite(self, buf: np.ndarray, path: str, offset: int = 0) -> int:
        return self.wait(self.async_pwrite(buf, path, offset))

    def sync_pread(self, buf: np.ndarray, path: str, offset: int = 0) -> int:
        return self.wait(self.async_pread(buf, path, offset))
