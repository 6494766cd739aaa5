"""ctypes binding to the native C++ runtime library (native/libtb_native.so).

The compute path is JAX/XLA; the runtime around it — checksums, durable
sector IO — is native C++ (the reference's analogs are Zig:
src/vsr/checksum.zig, src/storage.zig). The library is built on demand with
the baked-in g++ (no pip/pybind11 — plain ctypes over a C ABI).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libtb_native.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None


def _build() -> None:
    """Build the library when it is MISSING (a copy does not preserve
    mtimes, so staleness is not judged here: after editing a .cc run
    `make -C native`). The build runs under a lock file: a fresh checkout
    starts a server and a load generator together, and whichever comes
    second must wait for the finished library, not load a half-written
    one."""
    import fcntl

    with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if os.path.exists(_LIB_PATH):
            return
        done = subprocess.run(
            ["make", "-s", "libtb_native.so"], cwd=_NATIVE_DIR,
            capture_output=True, text=True,
        )
        if done.returncode != 0:
            raise RuntimeError(
                f"building {_LIB_PATH} failed:\n{done.stdout}{done.stderr}"
            )


def lib() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            _build()
            l = ctypes.CDLL(_LIB_PATH)
            l.tb_checksum.argtypes = [
                ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p
            ]
            l.tb_checksum.restype = None
            l.tb_storage_open.argtypes = [
                ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int
            ]
            l.tb_storage_open.restype = ctypes.c_int
            l.tb_storage_close.argtypes = [ctypes.c_int]
            l.tb_storage_close.restype = ctypes.c_int
            for fn in (l.tb_storage_write, l.tb_storage_read):
                fn.argtypes = [
                    ctypes.c_int, ctypes.c_uint64, ctypes.c_char_p,
                    ctypes.c_uint64,
                ]
                fn.restype = ctypes.c_int
            l.tb_storage_sync.argtypes = [ctypes.c_int]
            l.tb_storage_sync.restype = ctypes.c_int
            # native ledger engine (native/ledger.cc)
            l.tb_ledger_new.argtypes = [ctypes.c_int, ctypes.c_int]
            l.tb_ledger_new.restype = ctypes.c_void_p
            l.tb_ledger_free.argtypes = [ctypes.c_void_p]
            l.tb_ledger_free.restype = None
            l.tb_ledger_execute.argtypes = [
                ctypes.c_void_p, ctypes.c_uint8, ctypes.c_char_p,
                ctypes.c_uint32, ctypes.c_uint64, ctypes.c_void_p,
            ]
            l.tb_ledger_execute.restype = ctypes.c_int64
            l.tb_ledger_execute_group.argtypes = [
                ctypes.c_void_p, ctypes.c_uint8, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint32,
                ctypes.c_void_p, ctypes.c_void_p,
            ]
            l.tb_ledger_execute_group.restype = ctypes.c_int64
            l.tb_ledger_fingerprint.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p
            ]
            l.tb_ledger_fingerprint.restype = None
            l.tb_ledger_lookup.argtypes = [
                ctypes.c_void_p, ctypes.c_uint8, ctypes.c_char_p,
                ctypes.c_uint32, ctypes.c_void_p,
            ]
            l.tb_ledger_lookup.restype = ctypes.c_uint64
            l.tb_ledger_counts.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
            l.tb_ledger_counts.restype = None
            l.tb_ledger_snapshot_size.argtypes = [ctypes.c_void_p]
            l.tb_ledger_snapshot_size.restype = ctypes.c_uint64
            l.tb_ledger_snapshot.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
            l.tb_ledger_snapshot.restype = None
            l.tb_ledger_restore.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64
            ]
            l.tb_ledger_restore.restype = ctypes.c_int
            _lib = l
    return _lib


def checksum(data: bytes) -> int:
    """AEGIS-128L MAC checksum -> u128 (reference: src/vsr/checksum.zig:53).
    Every header, body, and block is guarded by this."""
    out = ctypes.create_string_buffer(16)
    lib().tb_checksum(bytes(data), len(data), out)
    return int.from_bytes(out.raw, "little")


CHECKSUM_BODY_EMPTY = 0x49F174618255402DE6E7E3C40D60CC83
"""checksum(b"") — pinned by the reference (src/vsr.zig:238)."""
