"""Native (C++) kernels for the host-side data path.

The TPU compute path is JAX/XLA; the host runtime around it (parsing, IO)
uses C++ where the reference did (dmlc-core's parsers are C++ too). Build is
lazy and cached: first use compiles the shared library with g++ next to this
package, under a file name that carries a hash of the sources — a library
built from other sources (a copied tree resets mtimes, so age proves
nothing) is simply never found. Any failure falls back to the pure-Python
implementations, so the framework never hard-requires a toolchain.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional, Sequence
from ..utils.locktrace import mutex

log = logging.getLogger("difacto_tpu")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = [os.path.join(_DIR, "libsvm_parser.cc"),
        os.path.join(_DIR, "criteo_parser.cc"),
        os.path.join(_DIR, "adfea_parser.cc")]

_lock = mutex()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def lib_path(srcs: Sequence[str] = _SRC) -> str:
    """Where the library built from exactly these source bytes lives.
    Raises OSError when a source is missing (partial checkout)."""
    h = hashlib.sha256()
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    return os.path.join(_DIR, f"_difacto_native-{h.hexdigest()[:16]}.so")


def _build(so: str) -> bool:
    # per-pid tmp so concurrent first-use builds in separate processes
    # can't interleave writes; os.replace is atomic
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", tmp] + _SRC
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        log.info("native build skipped (%s); using Python fallbacks", e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first use; None if
    unavailable (callers must fall back to Python)."""
    global _lib, _tried
    with _lock:
        if _lib is not None:
            return _lib
        if _tried:
            return None
        _tried = True
        try:
            so = lib_path()
        except OSError as e:
            log.info("native sources unreadable (%s); using Python "
                     "fallbacks", e)
            return None
        # the first-use build is serialized on purpose: every caller
        # needs its result anyway, and the compile is bounded by the
        # subprocess timeout=120 (concurrent PROCESS builders are
        # already safe via the per-pid tmp + atomic replace)
        # lint: ok(lock-blocking) intentional bounded build under the init lock
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            log.info("native load failed (%s); using Python fallbacks", e)
            return None
        lib.difacto_parse_libsvm.restype = ctypes.c_int
        lib.difacto_parse_libsvm.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.difacto_parse_criteo.restype = ctypes.c_int
        lib.difacto_parse_criteo.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.difacto_parse_adfea.restype = ctypes.c_int
        lib.difacto_parse_adfea.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.difacto_murmur64a.restype = ctypes.c_uint64
        lib.difacto_murmur64a.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint64]
        _lib = lib
        return _lib
