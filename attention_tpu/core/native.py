"""ctypes bridge to the native C oracle and testcase I/O (csrc/).

The reference keeps a compiled serial C implementation as its bit-level
oracle and CPU baseline (`attention.c`); this module provides the same
natively-compiled role for this framework.  The library is built on first
use with the system C compiler and cached next to the sources under a
name keyed by the sources' content and the host CPU (`_artifact_key`):
the build uses ``-march=native``, and a checkout copied to another
machine carries its untracked build products along, so an artefact is
only ever loaded by the kind of host that built it, from the sources it
sits beside.  Every entry point falls back to the NumPy implementations
in :mod:`attention_tpu.core` if no compiler is available
(:func:`oracle_in_use` says which ran, and the fallback is logged), so
the Python framework never hard-depends on the native path.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading

import numpy as np

_logger = logging.getLogger("attention_tpu.core.native")

_CSRC = os.path.join(os.path.dirname(__file__), "..", "..", "csrc")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_error: str | None = None


def _artifact_key(srcs: list[str]) -> str:
    """Short digest of the sources' bytes and the host CPU (machine,
    model, ISA flags) — the part of a build product's file name that
    ties it to the host kind and sources it was built from."""
    h = hashlib.sha256(platform.machine().encode())
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("model name", "flags", "Features")):
                    h.update(line.encode())
                    if line.startswith(("flags", "Features")):
                        break
    except OSError:
        pass
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:12]


def _compile(srcs: list[str], out_path: str, *, shared: bool) -> bool:
    """Try cc/gcc/clang in order; build to a pid-private temp and
    atomically rename.  Returns False (and cleans the temp) when no
    compiler works, a compiler hangs, or it errors."""
    tmp_path = f"{out_path}.{os.getpid()}.tmp"
    flags = ["-O3", "-march=native"]
    if shared:
        flags += ["-shared", "-fPIC"]
    for cc in ("cc", "gcc", "clang"):
        try:
            subprocess.run(
                [cc, *flags, *srcs, "-o", tmp_path, "-lm"],
                check=True, capture_output=True, timeout=120,
            )
            os.replace(tmp_path, out_path)
            return True
        except (FileNotFoundError, subprocess.CalledProcessError,
                subprocess.TimeoutExpired):
            if os.path.exists(tmp_path):
                try:
                    os.remove(tmp_path)
                except OSError:
                    pass
            continue
    return False


def _build_and_load() -> ctypes.CDLL | None:
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        csrc = os.path.abspath(_CSRC)
        src = os.path.join(csrc, "attention_serial.c")
        try:
            lib_path = os.path.join(
                csrc, f"libattn_serial.{_artifact_key([src])}.so")
            if not os.path.exists(lib_path) and not _compile(
                    [src], lib_path, shared=True):
                _build_error = "no working C compiler found"
            else:
                lib = ctypes.CDLL(lib_path)
        except OSError as e:  # load failure / missing sources
            _build_error = str(e)
        if _build_error is not None:
            _logger.warning(
                "native C oracle unavailable (%s); the NumPy oracle "
                "serves instead", _build_error)
            return None

        i64 = ctypes.c_int64
        dptr = np.ctypeslib.ndpointer(dtype=np.float64, flags="C_CONTIGUOUS")
        lib.attn_serial.argtypes = [
            dptr, dptr, dptr, dptr, i64, i64, i64, i64, ctypes.c_double,
        ]
        lib.attn_serial.restype = None
        lib.attn_verify.argtypes = [dptr, dptr, i64, ctypes.c_double]
        lib.attn_verify.restype = i64
        lib.attn_read_testcase.argtypes = [
            ctypes.c_char_p,
            np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS"),
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.attn_read_testcase.restype = ctypes.c_int
        _lib = lib
        return _lib


def native_available() -> bool:
    return _build_and_load() is not None


def oracle_in_use() -> str:
    """Which implementation serves `attention_native` in this process:
    the compiled C library (by file name) or the NumPy fallback (with
    the reason the build or load failed)."""
    lib = _build_and_load()
    if lib is None:
        return f"numpy fallback ({_build_error})"
    return f"native C ({os.path.basename(lib._name)})"


def attention_native(
    q: np.ndarray, k: np.ndarray, v: np.ndarray, *, scale: float | None = None
) -> np.ndarray:
    """fp64 serial attention through the compiled C oracle.

    Falls back to the NumPy oracle when the native library is unavailable.
    """
    lib = _build_and_load()
    q = np.ascontiguousarray(q, dtype=np.float64)
    k = np.ascontiguousarray(k, dtype=np.float64)
    v = np.ascontiguousarray(v, dtype=np.float64)
    if lib is None:
        from attention_tpu.core.oracle import attention_oracle

        return attention_oracle(q, k, v, scale=scale)
    m, dk = q.shape
    n, dv = v.shape
    if k.shape != (n, dk):
        raise ValueError(f"shape mismatch: Q{q.shape} K{k.shape} V{v.shape}")
    out = np.empty((m, dv), dtype=np.float64)
    lib.attn_serial(q, k, v, out, m, n, dk, dv, -1.0 if scale is None else scale)
    return out


def verify_native(
    result: np.ndarray, expected: np.ndarray, *, threshold: float = 0.02
) -> int:
    """First failing flat index, or -1 if within tolerance everywhere."""
    lib = _build_and_load()
    result = np.ascontiguousarray(result, dtype=np.float64)
    expected = np.ascontiguousarray(expected, dtype=np.float64)
    if result.shape != expected.shape:
        raise ValueError(f"shape mismatch {result.shape} vs {expected.shape}")
    if lib is None:
        bad = ~np.isfinite(result) | (np.abs(result - expected) > threshold)
        flat = np.flatnonzero(bad)
        return int(flat[0]) if flat.size else -1
    return int(lib.attn_verify(result.ravel(), expected.ravel(),
                               result.size, threshold))


def read_testcase_native(path: str):
    """Bulk-load a testcase through the native reader.

    Returns an ``attention_tpu.core.testcase.TestCase``; falls back to the
    NumPy reader without a native library.
    """
    from attention_tpu.core.testcase import TestCase, read_testcase

    lib = _build_and_load()
    if lib is None:
        return read_testcase(path)
    dims = np.zeros(4, dtype=np.int32)
    # first pass: header only, to size the buffers
    rc = lib.attn_read_testcase(path.encode(), dims, None, None, None, None)
    if rc == -1:
        raise FileNotFoundError(path)
    if rc in (-2, -3):
        raise ValueError(f"invalid testcase data in {path} (rc={rc})")
    m, n, dk, dv = (int(x) for x in dims)
    q = np.empty((m, dk))
    k = np.empty((n, dk))
    v = np.empty((n, dv))
    expected = np.empty((m, dv))
    rc = lib.attn_read_testcase(
        path.encode(), dims,
        q.ctypes.data_as(ctypes.c_void_p),
        k.ctypes.data_as(ctypes.c_void_p),
        v.ctypes.data_as(ctypes.c_void_p),
        expected.ctypes.data_as(ctypes.c_void_p),
    )
    if rc == -4:
        return TestCase(q=q, k=k, v=v, expected=None)
    if rc != 0:
        raise ValueError(f"invalid testcase data in {path} (rc={rc})")
    return TestCase(q=q, k=k, v=v, expected=expected)


_CLI_NAME = "attention_serial_cli"


def native_cli_path() -> str | None:
    """Build (if needed) and return the standalone native harness binary
    (`csrc/attention_main.c` — the reference's `./attention <case.bin>`
    CLI contract).  None when sources or a working C compiler are
    unavailable."""
    csrc = os.path.abspath(_CSRC)
    src_main = os.path.join(csrc, "attention_main.c")
    src_lib = os.path.join(csrc, "attention_serial.c")
    try:
        out = os.path.join(
            csrc, f"{_CLI_NAME}.{_artifact_key([src_main, src_lib])}")
    except OSError:
        return None
    if os.path.exists(out):
        return out
    return out if _compile([src_main, src_lib], out, shared=False) else None
