"""Native runtime components (C++ via ctypes).

The service plane's hot loops live here; JAX/XLA owns the device
compute path, C++ owns the host sequencing path (deli ticket —
SURVEY §3.1 marks it one of the three hot loops). The shared library
builds on demand with g++ and caches beside the source under a key
hashed from the source (``build_key``); every native
component keeps a pure-Python twin as both fallback and differential
oracle.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

_HERE = Path(__file__).parent
_SRC = _HERE / "sequencer.cpp"
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None
# per library: "built" (compiled by this process) or "reused" (a build
# whose key matches the source was already on disk)
_build_status: dict[str, str] = {}

_CXX = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC"]


def build_key(src: Path) -> str:
    """Content key of a native build: the compiler command and the
    source bytes. A ``.so`` is found only under its key, so one built
    from other source (a copied tree, a stale checkout) is never
    loaded."""
    digest = hashlib.sha256(" ".join(_CXX).encode())
    digest.update(src.read_bytes())
    return digest.hexdigest()[:16]


def _compile(src: Path, stem: str) -> tuple[Optional[Path], Optional[str]]:
    """Build ``src`` into ``<stem>.<key>.so`` beside it unless that
    file exists; returns ``(path, None)`` or ``(None, error)``."""
    out = src.parent / f"{stem}.{build_key(src)}.so"
    if out.exists():
        _build_status[stem] = "reused"
        return out, None
    # compile to a private name and rename: concurrent processes (test
    # workers) never see a half-written library under the keyed name
    tmp = out.with_name(f"{out.name}.{os.getpid()}.tmp")
    try:
        proc = subprocess.run(_CXX + [str(src), "-o", str(tmp)],
                              capture_output=True, text=True)
    except FileNotFoundError:
        return None, "g++ not found"
    if proc.returncode != 0:
        return None, proc.stderr[-2000:]
    os.replace(tmp, out)
    _build_status[stem] = "built"
    for stale in src.parent.glob(f"{stem}.*.so"):
        if stale != out:
            stale.unlink(missing_ok=True)
    return out, None


def native_build_status() -> dict[str, str]:
    """Which native libraries this process built or reused."""
    return dict(_build_status)


def _build() -> Optional[Path]:
    global _build_error
    path, err = _compile(_SRC, "_sequencer")
    if err is not None:
        _build_error = err
    return path


def load_native_sequencer() -> Optional[ctypes.CDLL]:
    """Build (if needed) + load the native core; None when the
    toolchain is unavailable (callers fall back to Python)."""
    global _lib, _build_error
    with _lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            return None  # failure is sticky: don't re-run g++ per call
        if os.environ.get("FFTPU_DISABLE_NATIVE") == "1":
            return None
        path = _build()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(str(path))
        except OSError as e:  # truncated/wrong-arch cached build
            _build_error = f"CDLL load failed: {e}"
            return None
        i64, p_i64 = ctypes.c_int64, ctypes.POINTER(ctypes.c_int64)
        p_i32 = ctypes.POINTER(ctypes.c_int32)
        lib.seq_create.restype = ctypes.c_void_p
        lib.seq_create.argtypes = [i64, i64]
        lib.seq_destroy.argtypes = [ctypes.c_void_p]
        lib.seq_client_join.restype = i64
        lib.seq_client_join.argtypes = [ctypes.c_void_p, i64]
        lib.seq_client_leave.restype = i64
        lib.seq_client_leave.argtypes = [ctypes.c_void_p, i64]
        for fn in ("seq_sequence_number", "seq_minimum_sequence_number",
                   "seq_client_count", "seq_bump"):
            getattr(lib, fn).restype = i64
            getattr(lib, fn).argtypes = [ctypes.c_void_p]
        lib.seq_ticket_batch.restype = i64
        lib.seq_ticket_batch.argtypes = [
            ctypes.c_void_p, i64, p_i64, p_i64, p_i64,
            p_i64, p_i64, p_i32,
        ]
        lib.seq_ticket_multi.restype = i64
        lib.seq_ticket_multi.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), i64, p_i64,
            p_i64, p_i64, p_i64, p_i64, p_i64, p_i32,
        ]
        lib.seq_export_clients.restype = i64
        lib.seq_export_clients.argtypes = [
            ctypes.c_void_p, i64, p_i64, p_i64, p_i64,
        ]
        lib.seq_restore_client.argtypes = [ctypes.c_void_p, i64, i64, i64]
        _lib = lib
        return _lib


def native_build_error() -> Optional[str]:
    return _build_error


_REPLAY_SRC = _HERE / "merge_replay.cpp"
_replay_lib: Optional[ctypes.CDLL] = None
_replay_error: Optional[str] = None


def load_merge_replay() -> Optional[ctypes.CDLL]:
    """Build + load the C++ scalar merge replayer (the compiled
    baseline); None when the toolchain is unavailable."""
    global _replay_lib, _replay_error
    with _lock:
        if _replay_lib is not None:
            return _replay_lib
        if _replay_error is not None:
            return None
        if os.environ.get("FFTPU_DISABLE_NATIVE") == "1":
            return None
        path, err = _compile(_REPLAY_SRC, "_merge_replay")
        if err is not None:
            _replay_error = err
            return None
        try:
            lib = ctypes.CDLL(str(path))
        except OSError as e:  # truncated/wrong-arch cached build
            _replay_error = f"CDLL load failed: {e}"
            return None
        i64 = ctypes.c_int64
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.merge_replay.restype = None
        lib.merge_replay.argtypes = [
            i32p, i64, i64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(i64),
        ]
        lib.merge_session_create.restype = ctypes.c_void_p
        lib.merge_session_create.argtypes = [i64]
        lib.merge_session_destroy.restype = None
        lib.merge_session_destroy.argtypes = [ctypes.c_void_p]
        lib.merge_session_apply.restype = None
        lib.merge_session_apply.argtypes = [
            ctypes.c_void_p, i32p, i32p, i64,
        ]
        lib.merge_session_stats.restype = None
        lib.merge_session_stats.argtypes = [
            ctypes.c_void_p, i64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(i64),
        ]
        lib.merge_session_segs.restype = i64
        lib.merge_session_segs.argtypes = [
            ctypes.c_void_p, i64, i32p, i64,
        ]
        _replay_lib = lib
        return _replay_lib


def merge_replay_error() -> Optional[str]:
    return _replay_error


from .sequencer_core import NativeSequencerCore  # noqa: E402

__all__ = [
    "NativeSequencerCore",
    "load_native_sequencer",
    "load_merge_replay",
    "merge_replay_error",
    "native_build_error",
    "native_build_status",
]
