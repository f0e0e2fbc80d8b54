"""mpicc — compiler wrapper for the C binding.

Reference: ompi/tools/wrappers (mpicc adds the include/lib flags so
`mpicc ring.c -o ring` just works). Here the wrapper additionally
builds the binding library itself on first use (the same on-demand
pattern as ompi_tpu/native/__init__.py) and bakes an rpath so the
produced binary runs without LD_LIBRARY_PATH:

    python -m ompi_tpu.tools.mpicc ring.c -o ring
    python -m ompi_tpu.tools.mpirun -np 4 ./ring

Pass ``--showme`` to print the flags instead of compiling (the
reference wrapper's introspection contract).
"""

from __future__ import annotations

import os
import subprocess
import sys
import sysconfig
import tempfile
from typing import List, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_NATIVE = os.path.join(os.path.dirname(_HERE), "native")
_CAPI_SRC = os.path.join(_NATIVE, "capi.c")


def _python_embed_flags() -> List[str]:
    """Include + link flags for embedding this interpreter (what
    `python3-config --includes --embed --ldflags` reports, but read
    from sysconfig so it matches THIS python even in venvs)."""
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") or \
        sysconfig.get_config_var("VERSION")
    flags = [f"-I{inc}"]
    if libdir:
        flags += [f"-L{libdir}", f"-Wl,-rpath,{libdir}"]
    flags += [f"-lpython{ver}", "-ldl", "-lm"]
    return flags


_CAPI_HDR = os.path.join(_NATIVE, "mpi.h")


def _safe_dir(d: str) -> bool:
    """Only trust/build in a dir we own that nobody else can write —
    a world-writable fallback would let another local user plant a
    libompi_tpu_c.so that gets rpath'd into the victim's binary."""
    try:
        st = os.stat(d)
    except OSError:
        return False
    return st.st_uid == os.getuid() and not (st.st_mode & 0o022)


def _lib_dirs() -> List[str]:
    """Candidate homes for libompi_tpu_c.so: next to the sources, then
    a per-user 0700 cache dir for read-only installs."""
    cache = os.environ.get("XDG_CACHE_HOME") or \
        os.path.join(os.path.expanduser("~"), ".cache")
    return [_NATIVE, os.path.join(cache, "ompi_tpu_c")]


def build_capi(cc: str = "cc") -> Optional[str]:
    """Compile libompi_tpu_c.so unless one built from exactly these
    sources exists (BOTH sources — a header edit must rebuild or the
    lib's struct offsets go stale); returns the path or None. Falls back
    to a per-user cache dir when the package directory is read-only."""
    srcs = [_CAPI_SRC, _CAPI_HDR]
    missing = [s for s in srcs if not os.path.exists(s)]
    if missing:
        sys.stderr.write(
            "mpicc: binding sources missing (%s) — reinstall with the "
            "package data intact\n" % ", ".join(missing))
        return None
    from ompi_tpu.native import compile_so, is_built

    cmd = [cc, "-O2", "-shared", "-fPIC", f"-I{_NATIVE}"] + \
        _python_embed_flags()
    for d in _lib_dirs():
        so = os.path.join(d, "libompi_tpu_c.so")
        if _safe_dir(d) and is_built(cmd, srcs, so):
            return so
    for d in _lib_dirs():
        try:
            os.makedirs(d, mode=0o700, exist_ok=True)
        except OSError:
            continue
        # skip unwritable/untrusted dirs BEFORE compiling: a genuine
        # compiler error must fail once, not be retried per dir
        if not (_safe_dir(d) and os.access(d, os.W_OK)):
            continue
        return compile_so(cmd, [_CAPI_SRC],
                          os.path.join(d, "libompi_tpu_c.so"),
                          deps=[_CAPI_HDR],
                          on_error=lambda m: sys.stderr.write(
                              f"mpicc: {m}\n"))
    sys.stderr.write("mpicc: no writable owner-only directory for "
                     "libompi_tpu_c.so\n")
    return None


def wrapper_flags(libdir: str = _NATIVE) -> List[str]:
    """The flags mpicc injects around the user's arguments."""
    return [f"-I{_NATIVE}", f"-L{libdir}", f"-Wl,-rpath,{libdir}",
            "-lompi_tpu_c"] + _python_embed_flags()


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    cc = os.environ.get("OMPI_TPU_CC", "cc")
    if "--showme" in argv:
        # point -L/-rpath at wherever the lib actually lives (a
        # read-only install builds into the cache dir, not _NATIVE)
        libdir = _NATIVE
        for d in _lib_dirs():
            if os.path.exists(os.path.join(d, "libompi_tpu_c.so")):
                libdir = d
                break
        print(" ".join([cc] + wrapper_flags(libdir)))
        return 0
    so = build_capi(cc)
    if so is None:
        return 1
    # user args first so their -o/-c land naturally; link flags last
    # (the classic wrapper ordering: libraries after objects)
    cmd = [cc] + argv + wrapper_flags(os.path.dirname(so))
    return subprocess.run(cmd).returncode


if __name__ == "__main__":
    sys.exit(main())
