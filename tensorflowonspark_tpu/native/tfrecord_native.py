"""ctypes binding for the C++ TFRecord codec (``tfrecord_codec.cc``).

Builds the shared library with g++ on first use (no pybind11 in the image —
the ABI is a 5-function ``extern "C"`` surface, so ctypes is the right-sized
binding).  The library's file name carries a hash of the source, so a build
is needed exactly when no library for THIS source exists — never judged by
mtimes, which a fresh copy of the tree flattens.  If the compiler or the
library is unavailable, ``available()`` is False,
:mod:`tensorflowonspark_tpu.tfrecord` stays on its pure-Python path, and
:func:`load_error` says why.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import mmap
import os
import subprocess
import threading

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "tfrecord_codec.cc")

_lock = threading.Lock()
_lib_state: list = []  # [CDLL_or_None] once probed
_load_error: list = []  # [reason] when the probe ended without a library


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"libtfrecord-{tag}.so")


def _build(lib_path: str) -> None:
    """Compile to a private name, then rename: concurrent first uses (one
    per executor) each publish a whole library or none."""
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load():
    if _lib_state:
        return _lib_state[0]
    with _lock:
        if _lib_state:
            return _lib_state[0]
        lib = None
        if os.environ.get("TFOS_DISABLE_NATIVE") == "1":
            _load_error.append("TFOS_DISABLE_NATIVE=1")
        else:
            try:
                lib_path = _lib_path()
                if not os.path.exists(lib_path):
                    _build(lib_path)
                lib = ctypes.CDLL(lib_path)
                u64p = ctypes.POINTER(ctypes.c_uint64)
                lib.tfr_write.restype = ctypes.c_long
                lib.tfr_write.argtypes = [
                    ctypes.c_char_p, ctypes.c_char_p, u64p, ctypes.c_long]
                lib.tfr_index.restype = ctypes.c_long
                lib.tfr_index.argtypes = [
                    ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int,
                    ctypes.POINTER(u64p), ctypes.POINTER(u64p)]
                lib.tfr_free.argtypes = [ctypes.c_void_p]
                lib.tfr_masked_crc.restype = ctypes.c_uint
                lib.tfr_masked_crc.argtypes = [
                    ctypes.c_char_p, ctypes.c_uint64]
            except (OSError, subprocess.SubprocessError) as e:
                # no g++, a failed build, or a library for another arch
                stderr = getattr(e, "stderr", b"") or b""
                _load_error.append(
                    f"{e!r} {stderr.decode(errors='replace')[-500:]}".strip())
                logger.info("native tfrecord codec unavailable (%s); "
                            "using Python", _load_error[0])
        _lib_state.append(lib)
        return lib


def available() -> bool:
    return _load() is not None


def load_error() -> str | None:
    """Why :func:`available` is False (None when the library loaded)."""
    _load()
    return _load_error[0] if _load_error else None


def masked_crc(data: bytes) -> int:
    return _load().tfr_masked_crc(data, len(data))


def write_records(path: str, records) -> int:
    """One C call per file: payloads are concatenated host-side."""
    lib = _load()
    records = [bytes(r) for r in records]
    blob = b"".join(records)
    n = len(records)
    lengths = (ctypes.c_uint64 * n)(*[len(r) for r in records])
    # fresh file semantics (tfr_write appends, matching Hadoop part writers)
    if os.path.exists(path):
        os.remove(path)
    written = lib.tfr_write(path.encode(), blob, lengths, n)
    if written != n:
        raise IOError(f"native TFRecord write to {path} failed")
    return written


def read_records(path: str, verify: bool = True):
    """mmap the file, index+verify in C, slice payloads in Python.

    MAP_PRIVATE copy-on-write mapping instead of ``f.read()`` so multi-GB
    part files never materialise fully in executor heap; pages stream
    through the page cache as the C indexer scans them.
    """
    lib = _load()
    with open(path, "rb") as f:
        try:
            mm = mmap.mmap(f.fileno(), 0, flags=mmap.MAP_PRIVATE,
                           prot=mmap.PROT_READ | mmap.PROT_WRITE)
        except ValueError:  # zero-length file: no records
            return
    try:
        size = len(mm)
        carr = (ctypes.c_char * size).from_buffer(mm)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        offsets, lengths = u64p(), u64p()
        try:
            n = lib.tfr_index(ctypes.addressof(carr), size, int(verify),
                              ctypes.byref(offsets), ctypes.byref(lengths))
            if n == -1:
                raise IOError(f"{path}: corrupt record crc")
            if n == -2:
                raise IOError(f"{path}: truncated record")
            for i in range(n):
                off, length = offsets[i], lengths[i]
                yield mm[off:off + length]
        finally:
            lib.tfr_free(offsets)
            lib.tfr_free(lengths)
            del carr  # release the buffer export before mm.close()
    finally:
        mm.close()
