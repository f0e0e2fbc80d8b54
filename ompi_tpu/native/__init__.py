"""Native (C++) components and their ctypes bindings.

The reference implements its transports, rings, and atomics in C
(opal/class/opal_fifo.c, btl/sm); this package holds the TPU framework's
C++ equivalents, compiled on demand with the system toolchain and loaded
via ctypes (no pybind11 in the image). Every native component has a
pure-Python fallback so the framework still runs where no compiler
exists — the fallback implements the exact same memory layout, so a
Python rank and a C++ rank can share one ring.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional

from ompi_tpu.utils.output import get_logger

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_HERE, "sm_ring.cpp"),
         os.path.join(_HERE, "convertor.cpp")]
_SO = os.path.join(_HERE, "_ompi_tpu_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _digest(cmd_prefix, srcs) -> str:
    """Hash of the compile command and the sources' contents: the key a
    built library is reused under (an mtime says nothing about a library
    that came along with a copied tree)."""
    h = hashlib.sha256("\0".join(cmd_prefix).encode())
    for p in srcs:
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def is_built(cmd_prefix, srcs, dest) -> bool:
    """True when ``dest`` was built by compile_so from exactly these
    sources with this command (its stamp file holds their digest)."""
    try:
        with open(dest + ".sha256") as f:
            stamp = f.read().strip()
    except OSError:
        return False
    return os.path.exists(dest) and stamp == _digest(cmd_prefix, srcs)


def compile_so(cmd_prefix, srcs, dest, timeout=180, on_error=None,
               deps=()):
    """Race-safe on-demand compile shared by every native lib: build to
    a private temp file in dest's directory, atomically rename into
    place (last writer wins; identical content makes the race
    harmless), then stamp it with the digest of the command, ``srcs``
    and ``deps`` (headers) for is_built. Returns dest or None; failures
    (including an unwritable destination directory) go through
    ``on_error(message)``."""
    report = on_error or (lambda m: get_logger("native").warning("%s", m))
    try:
        fd, tmp = tempfile.mkstemp(suffix=".so",
                                   dir=os.path.dirname(dest))
        os.close(fd)
    except OSError as e:
        report(f"cannot write {os.path.dirname(dest)}: {e}")
        return None
    try:
        subprocess.run(list(cmd_prefix) + list(srcs) + ["-o", tmp],
                       check=True, capture_output=True, text=True,
                       timeout=timeout)
        os.rename(tmp, dest)
        with open(tmp, "w") as f:
            f.write(_digest(cmd_prefix, list(srcs) + list(deps)))
        os.rename(tmp, dest + ".sha256")
        return dest
    except (subprocess.SubprocessError, OSError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        report(f"native build failed: {detail.strip()[:500]}")
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


_CMD = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17"]


def _build() -> bool:
    log = get_logger("native")
    return compile_so(
        _CMD, _SRCS, _SO,
        timeout=120,
        on_error=lambda m: log.warning(
            "%s (falling back to Python)", m)) is not None


def get_lib() -> Optional[ctypes.CDLL]:
    """The native library, building it if needed; None if unavailable."""
    global _lib, _lib_tried
    with _lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        if not is_built(_CMD, _SRCS, _SO) and not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            get_logger("native").warning("cannot load %s: %s", _SO, e)
            return None
        lib.smr_header_bytes.restype = ctypes.c_uint64
        lib.smr_init.restype = ctypes.c_int
        lib.smr_init.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.smr_capacity.restype = ctypes.c_uint64
        lib.smr_capacity.argtypes = [ctypes.c_void_p]
        lib.smr_push2.restype = ctypes.c_int
        lib.smr_push2.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                  ctypes.c_uint64, ctypes.c_void_p,
                                  ctypes.c_uint64]
        lib.smr_pop.restype = ctypes.c_int64
        lib.smr_pop.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                ctypes.c_uint64]
        lib.smr_peek.restype = ctypes.c_int64
        lib.smr_peek.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_uint64)]
        lib.smr_advance.restype = None
        for fn in (lib.ompi_tpu_pack_runs, lib.ompi_tpu_unpack_runs):
            fn.restype = None
            fn.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                           ctypes.c_void_p, ctypes.c_void_p,
                           ctypes.c_int64, ctypes.c_int64,
                           ctypes.c_int64]
        lib.smr_advance.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.smr_used.restype = ctypes.c_uint64
        lib.smr_used.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib
