"""Compile-on-demand loader for the native runtime library.

The reference builds its C++ runtime with CMake into static libs
(CMakeLists.txt:16-23); here the native pieces compile once into a shared
library next to the sources (g++ -O3 -shared), named by a hash of the sources
and flags, and load via ctypes.  If no toolchain is available — or the .so is
foreign — the callers fall back to pure-numpy implementations: the framework
stays functional, just with slower host-side generation.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["pool.cc", "datagen.cc"]
_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def library_path() -> str:
    """Where the library built from the current sources and flags lives.

    The name carries a hash of the committed sources and the compiler
    flags, so a library left over from other sources (an untracked, stale
    ``.so`` in the checkout) is never the one that loads, whatever its
    modification time."""
    h = hashlib.sha256(" ".join(_FLAGS).encode())
    for src in _SOURCES:
        with open(os.path.join(_DIR, src), "rb") as f:
            h.update(src.encode() + b"\0" + f.read())
    return os.path.join(_DIR, f"libtrj_native-{h.hexdigest()[:16]}.so")


def _compile() -> Optional[str]:
    out = library_path()
    if os.path.exists(out):
        return out
    # Compile to a temp path and rename into place so concurrent processes
    # never load a half-written library.
    tmp = f"{out}.tmp.{os.getpid()}"
    cmd = ["g++", *_FLAGS, "-o", tmp,
           *(os.path.join(_DIR, s) for s in _SOURCES)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
        return out
    except (subprocess.SubprocessError, FileNotFoundError, OSError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Declare signatures; raises AttributeError on missing symbols."""
    u64, u32, i32 = ctypes.c_uint64, ctypes.c_uint32, ctypes.c_int
    p_u32 = ctypes.POINTER(ctypes.c_uint32)
    lib.pool_create.restype = ctypes.c_void_p
    lib.pool_create.argtypes = [ctypes.c_size_t]
    lib.pool_get_memory.restype = ctypes.c_void_p
    lib.pool_get_memory.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    lib.pool_reset.argtypes = [ctypes.c_void_p]
    lib.pool_used.restype = ctypes.c_size_t
    lib.pool_used.argtypes = [ctypes.c_void_p]
    lib.pool_capacity.restype = ctypes.c_size_t
    lib.pool_capacity.argtypes = [ctypes.c_void_p]
    lib.pool_destroy.argtypes = [ctypes.c_void_p]
    lib.fill_unique.argtypes = [p_u32, u64, u64, u64, u32, p_u32, i32]
    lib.fill_modulo.argtypes = [p_u32, u64, u64, u32, i32]
    lib.fill_zipf.argtypes = [p_u32, u64, u64, p_u32, u64, p_u32, u64,
                              u64, i32]
    lib.fill_rids.argtypes = [p_u32, u64, u64, i32]
    return lib


def load() -> Optional[ctypes.CDLL]:
    """The native library, or None when unavailable (numpy fallbacks apply)."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        path = _compile()
        if path is None:
            return None
        try:
            # stale/foreign-arch .so or missing symbols: honor the numpy
            # fallback contract instead of crashing every caller
            _lib = _bind(ctypes.CDLL(path))
        except (OSError, AttributeError):
            _lib = None
        return _lib
