"""ctypes binding + lazy build for the native batch JPEG decoder.

No pybind11 in this environment; the C ABI (`ldt_decode_batch`) is bound via
ctypes. The shared library is compiled from ``ldt_decode.cpp`` on first use,
next to the source, under a name keyed by what went into it (the source's
content, the compile command, this host's CPU — see :func:`library_path`).
A build or load failure raises :class:`NativeBuildError` with the compiler's
message; the PIL path in :mod:`..data.decode` is reached only by asking for
it (``LDT_DISABLE_NATIVE=1`` or ``use_native=False``), never by a failure.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import tempfile
import threading
from typing import Optional, Sequence

import numpy as np

__all__ = [
    "NativeBuildError",
    "batch_decode_jpeg",
    "batch_decode_jpeg_arrow",
    "batch_probe_jpeg",
    "batch_extract_coeffs",
    "library_path",
    "native_available",
    "payload_pointers",
    "arrow_pointers",
]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "ldt_decode.cpp")
_ABI_VERSION = 3
_COMPILE = ("g++", "-O3", "-march=native", "-std=c++17", "-shared", "-fPIC")
_LINK = ("-ljpeg", "-pthread")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


class NativeBuildError(RuntimeError):
    """The decoder library could not be built or does not match its binding."""


def _cpu_identity() -> str:
    """What ``-march=native`` compiles for: this host's CPU model and its
    feature flags. A library built for another CPU can die with SIGILL."""
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            lines = {
                line for line in f
                if line.startswith(("model name", "flags", "Features"))
            }
    except OSError:
        lines = set()
    return platform.machine() + "".join(sorted(lines))


def library_path() -> str:
    """Where this host's build of ``ldt_decode.cpp`` lives: inside the
    checkout, named by a digest of the source, the compile command and the
    CPU. A copied tree's timestamps say nothing, so identity is content: a
    library from an older source or another machine has another name and
    is never loaded."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_COMPILE + _LINK).encode())
    h.update(_cpu_identity().encode())
    return os.path.join(_HERE, f"_ldt_decode-{h.hexdigest()[:16]}.so")


def _build(target: str) -> None:
    # Link into a private temp file, then rename over the target: concurrent
    # builders (spawned decode workers) never see a half-written library,
    # and the loser of the race just replaces it with its twin.
    fd, tmp = tempfile.mkstemp(dir=_HERE, prefix="_ldt_decode-",
                               suffix=".tmp")
    os.close(fd)
    cmd = [*_COMPILE, _SRC, "-o", tmp, *_LINK]
    try:
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=120)
        except (OSError, subprocess.TimeoutExpired) as e:
            raise NativeBuildError(
                f"could not run `{' '.join(cmd)}`: {e} (LDT_DISABLE_NATIVE=1 "
                "selects the PIL decoder on a machine with no toolchain)"
            ) from e
        if proc.returncode != 0:
            raise NativeBuildError(
                f"`{' '.join(cmd)}` exited {proc.returncode}:\n{proc.stderr}"
            )
        os.chmod(tmp, 0o755)  # mkstemp's 0600 would hide it from other users
        os.replace(tmp, target)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load() -> Optional[ctypes.CDLL]:
    global _lib
    with _lock:
        if _lib is not None or os.environ.get("LDT_DISABLE_NATIVE"):
            return _lib
        path = library_path()
        if not os.path.exists(path):
            _build(path)
        lib = ctypes.CDLL(path)
        if lib.ldt_decode_abi_version() != _ABI_VERSION:
            raise NativeBuildError(
                f"{path} reports ABI {lib.ldt_decode_abi_version()}, this "
                f"binding expects {_ABI_VERSION}: ldt_decode.cpp and "
                "native/jpeg.py disagree"
            )
        lib.ldt_decode_batch.restype = ctypes.c_int
        lib.ldt_decode_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int,
        ]
        lib.ldt_decode_batch_offsets.restype = ctypes.c_int
        lib.ldt_decode_batch_offsets.argtypes = [
            ctypes.c_void_p,  # values buffer
            ctypes.POINTER(ctypes.c_int64),  # offsets[n+1]
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int,
        ]
        lib.ldt_probe_batch.restype = ctypes.c_int
        lib.ldt_probe_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.ldt_extract_coeffs.restype = ctypes.c_int
        lib.ldt_extract_coeffs.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.c_int,
            ctypes.c_int,  # yb_h
            ctypes.c_int,  # yb_w
            ctypes.c_int,  # cb_h
            ctypes.c_int,  # cb_w
            ctypes.POINTER(ctypes.c_int16),
            ctypes.POINTER(ctypes.c_int16),
            ctypes.POINTER(ctypes.c_int16),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int,
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    """False only when ``LDT_DISABLE_NATIVE`` opts out; a decoder that
    should be there and cannot be built raises instead."""
    return _load() is not None


def _check_out(out: np.ndarray, n: int, out_size: int) -> np.ndarray:
    """Validate a caller-supplied output buffer (the pooled-page path,
    ``data/buffers.py``) before handing its pointer to C. The decoder
    writes ``n*out_size*out_size*3`` bytes unconditionally — a wrong shape,
    dtype or a non-contiguous view would be silent out-of-bounds writes."""
    expected = (n, out_size, out_size, 3)
    if out.dtype != np.uint8:
        raise ValueError(f"out buffer must be uint8, got {out.dtype}")
    if tuple(out.shape) != expected:
        raise ValueError(
            f"out buffer shape {tuple(out.shape)} != required {expected}"
        )
    if not out.flags["C_CONTIGUOUS"] or not out.flags["WRITEABLE"]:
        raise ValueError(
            "out buffer must be C-contiguous and writeable (pass a whole "
            "pooled page, not a view)"
        )
    return out


def batch_decode_jpeg(
    payloads: Sequence[bytes],
    out_size: int,
    n_threads: int = 0,
    out: Optional[np.ndarray] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Decode a batch of JPEG byte strings to ``[N, S, S, 3] uint8``.

    Returns ``(images, failed_mask)``; failed slots are zero-filled (caller
    may re-decode them via PIL). Raises ``RuntimeError`` if the native
    library is unavailable — check :func:`native_available` first.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native decoder unavailable")
    n = len(payloads)
    if out is None:
        out = np.empty((n, out_size, out_size, 3), dtype=np.uint8)
    else:
        _check_out(out, n, out_size)
    if n == 0:
        return out, np.zeros(0, np.uint8)
    srcs = (ctypes.c_char_p * n)(*payloads)
    lens = (ctypes.c_size_t * n)(*[len(p) for p in payloads])
    failed = np.zeros(n, dtype=np.uint8)
    lib.ldt_decode_batch(
        ctypes.cast(srcs, ctypes.POINTER(ctypes.c_char_p)),
        ctypes.cast(lens, ctypes.POINTER(ctypes.c_size_t)),
        n,
        out_size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        failed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n_threads,
    )
    return out, failed


def batch_decode_jpeg_arrow(
    binary_array,
    out_size: int,
    n_threads: int = 0,
    out: Optional[np.ndarray] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Decode an Arrow binary/large_binary array of JPEGs, zero-copy.

    Reads straight from the column's Arrow buffers (values + offsets) — no
    per-row Python ``bytes`` are materialised, unlike
    ``to_pylist()``-then-:func:`batch_decode_jpeg`. ``binary_array`` must be
    a non-chunked ``pyarrow.Array``; rows must be non-null.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native decoder unavailable")
    n = len(binary_array)
    if out is None:
        out = np.empty((n, out_size, out_size, 3), dtype=np.uint8)
    else:
        _check_out(out, n, out_size)
    if n == 0:
        return out, np.zeros(0, np.uint8)
    import pyarrow as pa

    buffers = binary_array.buffers()  # [validity, offsets, values]
    if buffers[0] is not None and binary_array.null_count:
        raise ValueError("null image rows are not decodable")
    width = 8 if pa.types.is_large_binary(binary_array.type) else 4
    raw = np.frombuffer(
        buffers[1], dtype=np.int64 if width == 8 else np.int32,
        count=binary_array.offset + n + 1,
    )
    offsets = np.ascontiguousarray(
        raw[binary_array.offset : binary_array.offset + n + 1], dtype=np.int64
    )
    failed = np.zeros(n, dtype=np.uint8)
    lib.ldt_decode_batch_offsets(
        ctypes.c_void_p(buffers[2].address),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        out_size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        failed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n_threads,
    )
    return out, failed


# -- entropy-boundary split (ABI v3) ----------------------------------------
#
# The host half of device-side decode: probe geometry, then extract the
# quantized DCT coefficient pages (jpeg_read_coefficients = the inherently
# sequential Huffman/entropy work ONLY). The dense back half — dequant,
# IDCT, chroma upsample, color convert, resize — is the jitted kernel in
# ops/jpeg_device.py. Both wrappers take a (srcs, lens, keepalive) pointer
# triple from payload_pointers/arrow_pointers so the arrow path never
# materialises per-row Python bytes.


def payload_pointers(payloads: Sequence[bytes]):
    """Pointer arrays over a list of JPEG byte strings. Returns
    ``(srcs, lens, n, keepalive)``; ``keepalive`` must outlive the call."""
    n = len(payloads)
    srcs = (ctypes.c_char_p * n)(*payloads)
    lens = (ctypes.c_size_t * n)(*[len(p) for p in payloads])
    return srcs, lens, n, payloads


def arrow_pointers(binary_array):
    """Pointer arrays straight over an Arrow binary column's buffers —
    zero-copy (no per-row ``bytes``); rows must be non-null."""
    import pyarrow as pa

    n = len(binary_array)
    buffers = binary_array.buffers()  # [validity, offsets, values]
    if buffers[0] is not None and binary_array.null_count:
        raise ValueError("null image rows are not decodable")
    width = 8 if pa.types.is_large_binary(binary_array.type) else 4
    raw = np.frombuffer(
        buffers[1], dtype=np.int64 if width == 8 else np.int32,
        count=binary_array.offset + n + 1,
    )
    offsets = raw[binary_array.offset : binary_array.offset + n + 1]
    base = buffers[2].address
    srcs = (ctypes.c_char_p * n)(
        *[ctypes.c_char_p(base + int(offsets[i])) for i in range(n)]
    )
    lens = (ctypes.c_size_t * n)(
        *[int(offsets[i + 1] - offsets[i]) for i in range(n)]
    )
    # Keep the Arrow buffers (and through them the column) alive for as
    # long as the pointer arrays are in use.
    return srcs, lens, n, buffers


def batch_probe_jpeg(pointers) -> tuple[np.ndarray, np.ndarray]:
    """Header-only parse of a batch: ``(geom [N,4] i32, failed [N] u8)``
    where geom rows are ``(width, height, ncomp, coeff_ok)``. ``coeff_ok``
    is 1 when the image is extractable into the canonical coefficient page
    (grayscale or 4:2:0 YCbCr)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native decoder unavailable")
    srcs, lens, n, keepalive = pointers
    geom = np.zeros((n, 4), dtype=np.int32)
    failed = np.zeros(n, dtype=np.uint8)
    if n:
        lib.ldt_probe_batch(
            ctypes.cast(srcs, ctypes.POINTER(ctypes.c_char_p)),
            ctypes.cast(lens, ctypes.POINTER(ctypes.c_size_t)),
            n,
            geom.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            failed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
    del keepalive
    return geom, failed


def _check_page(arr: np.ndarray, shape: tuple, dtype, name: str) -> None:
    """Validate a caller-supplied coefficient page before handing its
    pointer to C (same contract as :func:`_check_out`: exact shape/dtype,
    C-contiguous, writeable — anything else is a silent OOB write)."""
    if arr.dtype != dtype:
        raise ValueError(f"{name} must be {np.dtype(dtype)}, got {arr.dtype}")
    if tuple(arr.shape) != shape:
        raise ValueError(f"{name} shape {tuple(arr.shape)} != {shape}")
    if not arr.flags["C_CONTIGUOUS"] or not arr.flags["WRITEABLE"]:
        raise ValueError(f"{name} must be C-contiguous and writeable")


def batch_extract_coeffs(
    pointers,
    yb_h: int,
    yb_w: int,
    cb_h: int,
    cb_w: int,
    coef_y: np.ndarray,
    coef_cb: np.ndarray,
    coef_cr: np.ndarray,
    quant: np.ndarray,
    geom: np.ndarray,
    n_threads: int = 0,
) -> np.ndarray:
    """Entropy-decode a batch into caller-provided canonical pages.

    Pages may be pooled (``data/buffers.py``) and MUST be zeroed by the
    caller — padding blocks are never written by the extractor. Returns the
    per-image ``failed`` mask (corrupt or non-canonical sampling; those
    rows' page contents are unspecified but in-bounds)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native decoder unavailable")
    srcs, lens, n, keepalive = pointers
    _check_page(coef_y, (n, yb_h, yb_w, 64), np.int16, "coef_y")
    _check_page(coef_cb, (n, cb_h, cb_w, 64), np.int16, "coef_cb")
    _check_page(coef_cr, (n, cb_h, cb_w, 64), np.int16, "coef_cr")
    _check_page(quant, (n, 3, 64), np.int32, "quant")
    _check_page(geom, (n, 6), np.int32, "geom")
    failed = np.zeros(n, dtype=np.uint8)
    if n:
        lib.ldt_extract_coeffs(
            ctypes.cast(srcs, ctypes.POINTER(ctypes.c_char_p)),
            ctypes.cast(lens, ctypes.POINTER(ctypes.c_size_t)),
            n,
            yb_h,
            yb_w,
            cb_h,
            cb_w,
            coef_y.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
            coef_cb.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
            coef_cr.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
            quant.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            geom.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            failed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            n_threads,
        )
    del keepalive
    return failed
