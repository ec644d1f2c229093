"""Native (C++) runtime components.

The reference's control-plane/runtime native layer re-done for TPU:
TCPStore rendezvous (csrc/tcp_store.cpp) and the shared-memory dataloader
queue (csrc/shm_queue.cpp). Compiled on first use with g++ into a cached
shared library (no pip/pybind dependency; bindings are ctypes).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_LIB = None
_LOCK = threading.Lock()

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_BUILD_DIR = os.path.join(_SRC_DIR, "build")
_SOURCES = ["tcp_store.cpp", "shm_queue.cpp"]


def _build_lib() -> str:
    """Build (once per source content) and return the shared library.
    The file name carries a hash of the sources, so a binary built from
    other sources — left in a copied tree, whatever its mtime — is never
    picked up."""
    srcs = [os.path.join(_SRC_DIR, s) for s in _SOURCES]
    digest = hashlib.sha256()
    for src in srcs:
        with open(src, "rb") as f:
            digest.update(f.read())
    so_path = os.path.join(
        _BUILD_DIR, f"libpaddle_tpu_rt.{digest.hexdigest()[:16]}.so")
    if os.path.exists(so_path):
        return so_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # per-process temporary name: concurrent first builds (cluster worker
    # processes) must not write through each other before the rename
    tmp = f"{so_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
           *srcs, "-lrt", "-o", tmp]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, so_path)
    return so_path


def load_library() -> ctypes.CDLL:
    global _LIB
    with _LOCK:
        if _LIB is None:
            lib = ctypes.CDLL(_build_lib())
            # tcp_store
            lib.ts_server_start.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                            ctypes.POINTER(ctypes.c_void_p)]
            lib.ts_server_start.restype = ctypes.c_int
            lib.ts_server_stop.argtypes = [ctypes.c_void_p]
            lib.ts_client_connect.argtypes = [ctypes.c_char_p, ctypes.c_int]
            lib.ts_client_connect.restype = ctypes.c_int
            lib.ts_set.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                   ctypes.c_char_p, ctypes.c_int]
            lib.ts_set.restype = ctypes.c_int
            lib.ts_get.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                   ctypes.c_char_p, ctypes.c_int]
            lib.ts_get.restype = ctypes.c_int
            lib.ts_wait.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                    ctypes.c_int64, ctypes.c_char_p,
                                    ctypes.c_int]
            lib.ts_wait.restype = ctypes.c_int
            lib.ts_add.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                   ctypes.c_int64]
            lib.ts_add.restype = ctypes.c_int64
            lib.ts_delete.argtypes = [ctypes.c_int, ctypes.c_char_p]
            lib.ts_delete.restype = ctypes.c_int
            lib.ts_close.argtypes = [ctypes.c_int]
            # shm_queue
            lib.shmq_create.argtypes = [ctypes.c_char_p, ctypes.c_uint32,
                                        ctypes.c_uint32]
            lib.shmq_create.restype = ctypes.c_void_p
            lib.shmq_open.argtypes = [ctypes.c_char_p]
            lib.shmq_open.restype = ctypes.c_void_p
            lib.shmq_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_uint32, ctypes.c_int64]
            lib.shmq_push.restype = ctypes.c_int
            lib.shmq_pop.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_uint32, ctypes.c_int64]
            lib.shmq_pop.restype = ctypes.c_int
            lib.shmq_slot_size.argtypes = [ctypes.c_void_p]
            lib.shmq_slot_size.restype = ctypes.c_uint32
            lib.shmq_pending.argtypes = [ctypes.c_void_p]
            lib.shmq_pending.restype = ctypes.c_int
            lib.shmq_close.argtypes = [ctypes.c_void_p]
            _LIB = lib
    return _LIB


from paddle_tpu.native.tcp_store import TCPStore  # noqa: E402,F401
from paddle_tpu.native.shm_queue import ShmQueue  # noqa: E402,F401
