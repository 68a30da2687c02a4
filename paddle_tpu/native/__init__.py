"""Native runtime components (C++), built on demand with g++ and bound via
ctypes (no pybind11 dependency — SURVEY §2.6: native where the reference is
native: host tracer ≈ host_event_recorder.h, token feeder ≈ data_feed.cc).

`lib()` compiles paddle_tpu/native/*.cc into _native.<hash>.so on first
use and returns the ctypes handle, or None when no toolchain is
available — callers must degrade to their pure-Python path. The name
carries a hash of the sources' content, so a library built from other
sources (a stale file copied along with the tree, whatever its mtime)
is never loaded.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["host_tracer.cc", "token_feeder.cc", "tensor_store.cc"]

_lock = threading.Lock()
_lib = None
_tried = False


def _so_path() -> str:
    h = hashlib.sha256()
    for s in _SOURCES:
        with open(os.path.join(_DIR, s), "rb") as f:
            h.update(s.encode() + b"\0" + f.read())
    return os.path.join(_DIR, f"_native.{h.hexdigest()[:16]}.so")


def _build(so: str) -> bool:
    # compile to a per-pid temp then os.rename: atomic on POSIX, so
    # concurrent dp-rank processes never load a half-written .so
    srcs = [os.path.join(_DIR, s) for s in _SOURCES]
    tmp = f"{so}.tmp.{os.getpid()}"
    cmd = ["g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-pthread",
           *srcs, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
    except (OSError, subprocess.TimeoutExpired):
        return False
    if proc.returncode != 0:
        import logging
        logging.getLogger(__name__).warning(
            "native build failed; using pure-Python fallbacks:\n%s",
            proc.stderr[-2000:])
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    os.replace(tmp, so)
    for old in glob.glob(os.path.join(_DIR, "_native*.so")):
        if old != so:  # built from sources that are gone
            try:
                os.unlink(old)
            except OSError:
                pass
    return True


def _bind(handle: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    # host tracer
    handle.pt_record_begin.argtypes = [c.c_char_p]
    handle.pt_record_instant.argtypes = [c.c_char_p, c.c_int64]
    handle.pt_now_ns.restype = c.c_uint64
    handle.pt_tracer_enabled.restype = c.c_int
    handle.pt_collect.restype = c.c_void_p
    handle.pt_collect.argtypes = [c.POINTER(c.POINTER(CollectedEvent)),
                                  c.POINTER(c.c_uint64)]
    handle.pt_free_events.argtypes = [c.c_void_p]
    # token feeder
    handle.pt_feeder_create.restype = c.c_void_p
    handle.pt_feeder_create.argtypes = [
        c.c_char_p, c.c_int64, c.c_int64, c.c_int64, c.c_uint64,
        c.c_int64, c.c_int64, c.c_int64, c.c_int]
    handle.pt_feeder_num_batches.restype = c.c_int64
    handle.pt_feeder_num_batches.argtypes = [c.c_void_p]
    handle.pt_feeder_samples_total.restype = c.c_int64
    handle.pt_feeder_samples_total.argtypes = [c.c_void_p]
    handle.pt_feeder_next.restype = c.c_int
    handle.pt_feeder_next.argtypes = [c.c_void_p,
                                      c.POINTER(c.c_int32)]
    handle.pt_feeder_next_epoch.argtypes = [c.c_void_p]
    handle.pt_feeder_destroy.argtypes = [c.c_void_p]
    # tensor store (checkpoint blobs)
    handle.pts_writer_open.restype = c.c_void_p
    handle.pts_writer_open.argtypes = [c.c_char_p, c.c_int]
    handle.pts_writer_add.restype = c.c_int
    handle.pts_writer_add.argtypes = [
        c.c_void_p, c.c_char_p, c.c_char_p, c.c_int,
        c.POINTER(c.c_int64), c.c_void_p, c.c_int64]
    handle.pts_writer_close.restype = c.c_int
    handle.pts_writer_close.argtypes = [c.c_void_p]
    handle.pts_reader_open.restype = c.c_void_p
    handle.pts_reader_open.argtypes = [c.c_char_p]
    handle.pts_reader_count.restype = c.c_int64
    handle.pts_reader_count.argtypes = [c.c_void_p]
    handle.pts_reader_error.restype = c.c_char_p
    handle.pts_reader_error.argtypes = [c.c_void_p]
    handle.pts_reader_name.restype = c.c_char_p
    handle.pts_reader_name.argtypes = [c.c_void_p, c.c_int64]
    handle.pts_reader_dtype.restype = c.c_char_p
    handle.pts_reader_dtype.argtypes = [c.c_void_p, c.c_int64]
    handle.pts_reader_ndim.restype = c.c_int
    handle.pts_reader_ndim.argtypes = [c.c_void_p, c.c_int64]
    handle.pts_reader_shape.argtypes = [c.c_void_p, c.c_int64,
                                        c.POINTER(c.c_int64)]
    handle.pts_reader_nbytes.restype = c.c_int64
    handle.pts_reader_nbytes.argtypes = [c.c_void_p, c.c_int64]
    handle.pts_reader_read.restype = c.c_int
    handle.pts_reader_read.argtypes = [c.c_void_p, c.c_int64,
                                       c.c_void_p]
    handle.pts_reader_close.argtypes = [c.c_void_p]
    return handle


class CollectedEvent(ctypes.Structure):
    _fields_ = [
        ("name", ctypes.c_char_p),
        ("start_ns", ctypes.c_uint64),
        ("end_ns", ctypes.c_uint64),
        ("tid", ctypes.c_uint64),
        ("mem_bytes", ctypes.c_int64),
    ]


def lib():
    """The ctypes handle to the native library, building if needed;
    None if the toolchain or build is unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            _lib = _bind(ctypes.CDLL(so))
        except OSError:
            _lib = None
    return _lib


def available() -> bool:
    return lib() is not None
