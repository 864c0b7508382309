"""ctypes bridge to the native (C++) data-path library, with lazy on-demand
compilation. The loader never requires the native path: when the library
cannot be built or loaded, ONE warning says why and numpy serves the same
bytes."""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger("tpuddp")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "gather.cpp")


def _isa_tag() -> str:
    """Host ISA fingerprint for the cached-library filename. The build uses
    ``-march=native``, so on a shared filesystem a .so built on a newer-ISA
    node would SIGILL when dlopen'd on an older one — keying the cache path
    by machine + CPU-flags hash makes each ISA build its own copy."""
    flags = b""
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith((b"flags", b"Features")):
                    flags = b" ".join(sorted(line.split(b":", 1)[1].split()))
                    break
    except OSError:
        pass
    digest = hashlib.sha256(flags).hexdigest()[:8]
    return f"{platform.machine()}-{digest}"


_LIB = os.path.join(_DIR, f"libtpuddp_gather.{_isa_tag()}.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    # Compile to a temp path and rename into place: concurrent first-use
    # builders (multi-job shared filesystems) and mid-write kills must never
    # leave a half-written .so for another process to dlopen.
    tmp = f"{_LIB}.tmp.{os.getpid()}"
    cmd = [
        "g++", "-O3", "-march=native", "-shared", "-fPIC",
        _SRC, "-o", tmp, "-lpthread",
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB)
        return True
    except Exception as e:
        stderr = getattr(e, "stderr", None) or b""
        logger.warning(
            "native gather build failed (%s %s); the loader gathers in numpy",
            e, stderr.decode(errors="replace").strip()[-400:],
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def load() -> Optional[ctypes.CDLL]:
    """The native library, building it on first use; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            fresh = (
                os.path.exists(_LIB)
                and os.path.getmtime(_LIB) >= os.path.getmtime(_SRC)
            )
        except OSError:  # e.g. stale .so present but source missing
            fresh = os.path.exists(_LIB)
        if not fresh and not _build():
            return None
        try:
            lib = ctypes.CDLL(_LIB)
            lib.tpuddp_gather_rows.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int,
            ]
            lib.tpuddp_gather_rows.restype = None
            lib.tpuddp_native_abi_version.restype = ctypes.c_int
            assert lib.tpuddp_native_abi_version() == 1
            _lib = lib
        except Exception as e:  # pragma: no cover - load failure path
            logger.warning(
                "native gather load failed (%s); the loader gathers in numpy", e
            )
            _lib = None
        return _lib


def available() -> bool:
    return load() is not None


def gather_rows(src: np.ndarray, indices: np.ndarray, pad_rows: int = 0) -> Optional[np.ndarray]:
    """Gather ``src[indices]`` (rows of an (N, ...) array) with optional
    padding to ``pad_rows`` rows by repeating the first gathered row.
    Returns None when the native path can't serve this input (caller falls
    back to numpy)."""
    lib = load()
    if lib is None or not src.flags["C_CONTIGUOUS"] or len(src) == 0:
        return None
    idx = np.ascontiguousarray(indices, dtype=np.int64)
    n = len(idx)
    if n == 0:
        # the C side has no source row to replicate as padding; let the
        # numpy fallback produce the (deterministic) empty/padded result
        return None
    if int(idx.min()) < 0 or int(idx.max()) >= len(src):
        # out-of-range (incl. negative, which numpy would wrap) -> numpy
        # fallback, which raises a clean IndexError instead of a wild memcpy
        return None
    out_rows = max(n, pad_rows)
    row_bytes = src.strides[0]
    out = np.empty((out_rows,) + src.shape[1:], dtype=src.dtype)
    lib.tpuddp_gather_rows(
        src.ctypes.data, row_bytes,
        idx.ctypes.data, n, out_rows,
        out.ctypes.data, 0,
    )
    return out
