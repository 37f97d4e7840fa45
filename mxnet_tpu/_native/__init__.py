"""Native (C++) components, built on demand with the system toolchain.

The reference implements its IO hot paths in C++ (recordio container
scanning, image record iterators — ``src/io/``); this package holds the
TPU-native equivalents. Each .so is compiled lazily from the checked-in
source on first use and cached next to it; every consumer has a pure-
Python fallback so a missing toolchain degrades gracefully.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

_log = logging.getLogger(__name__)
_lock = threading.Lock()
_libs = {}


def load(name: str):
    """Compile (once) and dlopen _native/<name>.cpp. None if unavailable."""
    with _lock:
        if name in _libs:
            return _libs[name]
        here = os.path.dirname(os.path.abspath(__file__))
        src = os.path.join(here, f"{name}.cpp")
        so = os.path.join(here, f"lib{name}.so")
        lib = None
        try:
            if (not os.path.exists(so)
                    or os.path.getmtime(so) < os.path.getmtime(src)):
                # per-process temp name: concurrent first-use from several
                # worker processes must not clobber each other's output
                import tempfile

                fd, tmp = tempfile.mkstemp(suffix=".so", dir=here)
                os.close(fd)
                cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                       src, "-o", tmp]
                subprocess.run(cmd, check=True, capture_output=True,
                               timeout=120)
                os.replace(tmp, so)
            lib = ctypes.CDLL(so)
        except Exception as e:  # noqa: BLE001 - any build/load failure
            _log.warning("native lib%s.so unavailable (%r); its consumers "
                         "take their pure-Python path", name, e)
            lib = None
        _libs[name] = lib
        return lib


def recordio_lib():
    lib = load("recordio")
    if lib is None:
        return None
    if not getattr(lib, "_sigs_set", False):
        u64, p = ctypes.c_uint64, ctypes.c_void_p
        lib.rio_open.restype = p
        lib.rio_open.argtypes = [ctypes.c_char_p]
        lib.rio_create.restype = p
        lib.rio_create.argtypes = [ctypes.c_char_p]
        lib.rio_close.argtypes = [p]
        lib.rio_seek.argtypes = [p, u64]
        lib.rio_tell.argtypes = [p]
        lib.rio_tell.restype = u64
        lib.rio_next.argtypes = [p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))]
        lib.rio_next.restype = u64
        lib.rio_write.argtypes = [p, ctypes.c_char_p, u64]
        lib.rio_write.restype = u64
        lib.rio_flush.argtypes = [p]
        lib.rio_build_index.argtypes = [ctypes.c_char_p,
                                        ctypes.POINTER(ctypes.POINTER(u64))]
        lib.rio_build_index.restype = u64
        lib.rio_free_index.argtypes = [ctypes.POINTER(u64)]
        lib._sigs_set = True
    return lib
