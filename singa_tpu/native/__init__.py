"""Native (C++) runtime components, built on demand with g++.

Each component is one .cc compiled into a cached shared object and loaded
via ctypes (this environment has no pybind11; ctypes IS the binding
layer). Loaders return None when no compiler is available — callers then
use their pure-Python fallback paths; `lib() is not None` says which of
the two a machine got.

Components:
- recordio.cc  -> lib():          threaded-prefetch record IO (data plane)
- snapshot.cc  -> snapshot_lib(): binfile tensor kv-store with a
                                  background flush thread (checkpoint
                                  plane, ref src/io/snapshot.cc)
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))

_lock = threading.Lock()
_libs: dict = {}


def _compile(name: str) -> str | None:
    """Path of the shared object built from the committed `name`.cc, or
    None with no compiler. The file name carries a hash of the source,
    so a `lib*.so` that came along with a copied tree (whose mtimes say
    nothing) is loaded only if it was built from exactly this source."""
    src = os.path.join(_DIR, name + ".cc")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    so = os.path.join(_DIR, f"lib{name}-{digest}.so")
    if os.path.exists(so):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
             src, "-o", tmp],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError):
        return None
    return so


def _load(name: str, annotate) -> "ctypes.CDLL | None":
    with _lock:
        if name in _libs:
            return _libs[name]
        so = _compile(name)
        lb = None
        if so is not None:
            lb = ctypes.CDLL(so)
            annotate(lb)
        _libs[name] = lb
        return lb


def _annotate_recordio(lb):
    lb.rio_writer_open.restype = ctypes.c_void_p
    lb.rio_writer_open.argtypes = [ctypes.c_char_p]
    lb.rio_writer_write.restype = ctypes.c_int
    lb.rio_writer_write.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
        ctypes.c_char_p, ctypes.c_uint64]
    lb.rio_writer_close.restype = ctypes.c_int
    lb.rio_writer_close.argtypes = [ctypes.c_void_p]
    lb.rio_reader_open.restype = ctypes.c_void_p
    lb.rio_reader_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lb.rio_reader_next.restype = ctypes.c_int
    lb.rio_reader_next.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint64)]
    lb.rio_reader_close.restype = None
    lb.rio_reader_close.argtypes = [ctypes.c_void_p]


def _annotate_snapshot(lb):
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lb.snp_writer_open.restype = ctypes.c_void_p
    lb.snp_writer_open.argtypes = [ctypes.c_char_p]
    lb.snp_writer_write.restype = ctypes.c_int
    lb.snp_writer_write.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_uint8, u64p, ctypes.c_char_p, ctypes.c_uint64]
    lb.snp_writer_close.restype = ctypes.c_int
    lb.snp_writer_close.argtypes = [ctypes.c_void_p]
    lb.snp_reader_open.restype = ctypes.c_void_p
    lb.snp_reader_open.argtypes = [ctypes.c_char_p]
    lb.snp_reader_next.restype = ctypes.c_int
    lb.snp_reader_next.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(u64p),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint64)]
    lb.snp_reader_close.restype = None
    lb.snp_reader_close.argtypes = [ctypes.c_void_p]


def lib():
    """Record-IO library, or None if unavailable."""
    return _load("recordio", _annotate_recordio)


def snapshot_lib():
    """Snapshot binfile library, or None if unavailable."""
    return _load("snapshot", _annotate_snapshot)
