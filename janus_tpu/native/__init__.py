"""Native (C) host runtime pieces, loaded via ctypes.

The reference's host-side hot code is native Rust (the prio crate's XOF
expansion and codec, SURVEY.md section 2.2); this package holds the TPU
build's native equivalents. The shared library is compiled on first use
with the system compiler and cached next to the sources under a name
keyed on the hash of the source, so only a library built from the
committed xof.c is ever loaded; everything has
a pure-Python fallback so the framework still works where no compiler
is available (`native.available()` reports which path is active).

Current contents:
  - xof.c — Keccak-f[1600]/SHAKE128 batch seed expansion with
    oversample-and-reduce field sampling (8*(limbs+1) stream bytes per
    element, reduced mod p) into u64 limb buffers (pthread-parallel
    across seeds), byte-compatible with janus_tpu.vdaf.xof.XofCtr128
    (counter-mode framing with tree-digested long binders).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "xof.c")


def _lib_name() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return f"libjanus_native-{sys.implementation.cache_tag}-{digest}.so"


_lock = threading.Lock()
_lib = None
_tried = False


def _build(lib_path: str) -> bool:
    for cc in ("cc", "gcc", "clang", "g++"):
        try:
            # atomic publish: build to a temp name, rename into place
            fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
            os.close(fd)
            r = subprocess.run(
                [cc, "-O3", "-shared", "-fPIC", "-o", tmp, _SRC, "-lpthread"],
                capture_output=True,
                timeout=120,
            )
            if r.returncode == 0:
                os.replace(tmp, lib_path)
                return True
            os.unlink(tmp)
        except (OSError, subprocess.TimeoutExpired):
            continue
    return False


def _load():
    global _lib, _tried
    if _lib is not None:  # lock-free fast path once loaded
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            lib_path = os.path.join(_DIR, _lib_name())
            if not os.path.exists(lib_path) and not _build(lib_path):
                return None
            lib = ctypes.CDLL(lib_path)
        except OSError:
            return None
        lib.janus_shake128.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
        ]
        lib.janus_expand_field_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_int,
            ctypes.c_uint64,
            ctypes.c_uint64,
            ctypes.c_void_p,
            ctypes.c_int,
        ]
        lib.janus_expand_field_batch.restype = ctypes.c_int
        lib.janus_derive_seed_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
        ]
        lib.janus_derive_seed_batch.restype = ctypes.c_int
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def shake128(data: bytes, outlen: int) -> bytes | None:
    lib = _load()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(outlen)
    lib.janus_shake128(data, len(data), out, outlen)
    return out.raw


def _n_threads(n: int, length: int) -> int:
    # one squeeze block ~ 21 permutations/KB; threading pays off quickly
    work = n * max(length, 1)
    if work < 2048:
        return 1
    return min(os.cpu_count() or 1, 16, n)


def expand_field_batch(
    dst16: bytes,
    seeds: np.ndarray | list[bytes],
    binders: np.ndarray | list[bytes] | None,
    length: int,
    limbs: int,
    modulus: int,
) -> np.ndarray | None:
    """Expand n seeds into an [n, length, limbs] u64 array, or None if the
    native library is unavailable. seeds: [n,16] u8 (or list of 16-byte
    strings); binders: [n, binder_len] u8 / list / None."""
    lib = _load()
    if lib is None:
        return None
    if not isinstance(seeds, np.ndarray):
        seeds = np.frombuffer(b"".join(seeds), dtype=np.uint8).reshape(-1, 16)
    seeds = np.ascontiguousarray(seeds, dtype=np.uint8)
    n = seeds.shape[0]
    if binders is not None and not isinstance(binders, np.ndarray):
        joined = b"".join(binders)
        blen = len(joined) // n if n else 0
        binders = np.frombuffer(joined, dtype=np.uint8).reshape(n, blen)
    if binders is not None:
        binders = np.ascontiguousarray(binders, dtype=np.uint8)
        bptr = binders.ctypes.data_as(ctypes.c_void_p)
        blen = binders.shape[1]
    else:
        bptr, blen = None, 0
    out = np.empty((n, length, limbs), dtype=np.uint64)
    rc = lib.janus_expand_field_batch(
        dst16,
        seeds.ctypes.data_as(ctypes.c_void_p),
        n,
        bptr,
        blen,
        length,
        limbs,
        ctypes.c_uint64(modulus & 0xFFFFFFFFFFFFFFFF),
        ctypes.c_uint64(modulus >> 64),
        out.ctypes.data_as(ctypes.c_void_p),
        _n_threads(n, length),
    )
    if rc != 0:
        return None
    return out


def derive_seed_batch(
    dst16: bytes,
    seeds: np.ndarray | list[bytes],
    binders: np.ndarray | list[bytes] | None,
) -> np.ndarray | None:
    """out[i] = SHAKE128(dst16 || seed_i || binder_i)[:16] as [n,16] u8."""
    lib = _load()
    if lib is None:
        return None
    if not isinstance(seeds, np.ndarray):
        seeds = np.frombuffer(b"".join(seeds), dtype=np.uint8).reshape(-1, 16)
    seeds = np.ascontiguousarray(seeds, dtype=np.uint8)
    n = seeds.shape[0]
    if binders is not None and not isinstance(binders, np.ndarray):
        joined = b"".join(binders)
        blen = len(joined) // n if n else 0
        binders = np.frombuffer(joined, dtype=np.uint8).reshape(n, blen)
    if binders is not None:
        binders = np.ascontiguousarray(binders, dtype=np.uint8)
        bptr = binders.ctypes.data_as(ctypes.c_void_p)
        blen = binders.shape[1]
    else:
        bptr, blen = None, 0
    out = np.empty((n, 16), dtype=np.uint8)
    rc = lib.janus_derive_seed_batch(
        dst16,
        seeds.ctypes.data_as(ctypes.c_void_p),
        n,
        bptr,
        blen,
        out.ctypes.data_as(ctypes.c_void_p),
    )
    if rc != 0:
        return None
    return out
