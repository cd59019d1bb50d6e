"""Native (C++) runtime components, bound via ctypes.

The reference keeps its IO hot paths in C++ (dmlc-core recordio,
src/io/iter_image_recordio_2.cc); this module is the TPU rebuild's native
seam: a small C ABI (mxnet_tpu/src/*.cc) compiled on demand with g++ and
loaded with ctypes — no pybind11 dependency, and the C boundary stays as
language-portable as the reference's C API.

Build-on-first-use into ONE directory: ``$MXNET_NATIVE_CACHE`` if set,
else ``mxnet_tpu/src/build/`` inside the checkout (git-ignored).  A built
library is reused only while the sha256 of its source and compile flags,
stored beside it, still matches — never by mtime, which a copy of the
tree changes.  Every entry point has a pure-python fallback: a host
without the toolchain gets a RuntimeWarning and the slow lane
(``MXNET_USE_NATIVE=0`` disables the native lane outright).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings

import numpy as _np

from . import config

__all__ = ["recordio_lib", "native_available", "index_recordio",
           "read_recordio_batch"]

_lock = threading.Lock()
_lib = None
_tried = False

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src",
                    "recordio.cc")

_ERRORS = {
    -1: "cannot open file",
    -2: "bad record framing (magic/length mismatch)",
    -3: "split (multi-chunk) records not supported by the native scanner",
    -4: "I/O error",
    -5: "output buffer too small",
    -6: "out of memory",
}


def _build_dir():
    return config.get("MXNET_NATIVE_CACHE") \
        or os.path.join(os.path.dirname(_SRC), "build")


def _replace_atomically(path, write):
    """Write ``path`` through a unique temp name and an atomic rename:
    concurrent workers (tools/launch.py, pytest-xdist) must never read a
    half-written file."""
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        write(tmp)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)   # a failed/timed-out build must not litter


def _build(name, src, extra_link=()):
    """Path of the up-to-date shared library for ``src``, compiling it
    unless the stored digest of (source, command) still matches."""
    out = os.path.join(_build_dir(), name)
    stamp = out + ".sha256"
    flags = ["-O3", "-shared", "-fPIC", "-std=c++17"]
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read() + bytes(
            " ".join([*flags, *extra_link]), "utf-8")).hexdigest()
    try:
        with open(stamp) as f:
            if f.read().strip() == digest and os.path.exists(out):
                return out
    except OSError:
        pass
    os.makedirs(os.path.dirname(out), exist_ok=True)
    _replace_atomically(out, lambda tmp: subprocess.run(
        ["g++", *flags, "-o", tmp, src, *extra_link],
        check=True, capture_output=True, timeout=120))

    def _write_stamp(tmp):
        with open(tmp, "w") as f:
            f.write(digest + "\n")
    _replace_atomically(stamp, _write_stamp)
    return out


def _load(name, src, bind, extra_link=()):
    """Build and bind one library; None (with a warning naming the cause)
    when this host cannot build or load it."""
    try:
        return bind(_build(name, src, extra_link))
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        warnings.warn(
            f"mxnet_tpu.native: {name} unavailable, using the pure-python "
            f"path ({type(e).__name__}: {e} "
            f"{detail.decode(errors='replace')[-300:]})",
            RuntimeWarning, stacklevel=3)
        return None


def _bind(path):
    lib = ctypes.CDLL(path)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.rio_index.argtypes = [ctypes.c_char_p, ctypes.POINTER(u64p),
                              ctypes.POINTER(u64p),
                              ctypes.POINTER(ctypes.c_uint64)]
    lib.rio_index.restype = ctypes.c_int
    lib.rio_read_batch.argtypes = [
        ctypes.c_char_p, u64p, u64p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64)]
    lib.rio_read_batch.restype = ctypes.c_int
    lib.rio_free.argtypes = [ctypes.c_void_p]
    lib.rio_free.restype = None
    return lib


def recordio_lib():
    """The bound native library, building it on first use; None when the
    toolchain/lib is unavailable or MXNET_USE_NATIVE=0."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not config.get_int("MXNET_USE_NATIVE", 1):
            return None
        _lib = _load("librecordio.so", _SRC, _bind)
        return _lib


def native_available():
    return recordio_lib() is not None


def _check(rc, what):
    if rc != 0:
        from .base import MXNetError
        raise MXNetError(
            f"native recordio {what}: {_ERRORS.get(rc, f'error {rc}')}")


def index_recordio(path):
    """Scan a .rec file natively → (offsets, lengths) uint64 ndarrays of
    payload positions.  Raises on malformed files; returns None when the
    native lib is unavailable (caller falls back to python scanning)."""
    lib = recordio_lib()
    if lib is None:
        return None
    u64p = ctypes.POINTER(ctypes.c_uint64)
    offs, lens = u64p(), u64p()
    count = ctypes.c_uint64()
    rc = lib.rio_index(path.encode(), ctypes.byref(offs),
                       ctypes.byref(lens), ctypes.byref(count))
    _check(rc, "index")
    n = count.value
    try:
        o = _np.ctypeslib.as_array(offs, shape=(n,)).copy() if n else \
            _np.empty((0,), _np.uint64)
        l = _np.ctypeslib.as_array(lens, shape=(n,)).copy() if n else \
            _np.empty((0,), _np.uint64)
    finally:
        # rio_index mallocs unconditionally (malloc(0) may return non-null),
        # so free unconditionally — an n == 0 guard leaks two allocations
        # per empty-file scan
        lib.rio_free(offs)
        lib.rio_free(lens)
    return o, l


def read_recordio_batch(path, offsets, lengths):
    """Bulk-read payloads at (offsets, lengths) → list of bytes.  Returns
    None when the native lib is unavailable."""
    lib = recordio_lib()
    if lib is None:
        return None
    offsets = _np.ascontiguousarray(offsets, _np.uint64)
    lengths = _np.ascontiguousarray(lengths, _np.uint64)
    total = int(lengths.sum())
    out = _np.empty((total,), _np.uint8)
    written = ctypes.c_uint64()
    u64p = ctypes.POINTER(ctypes.c_uint64)
    rc = lib.rio_read_batch(
        path.encode(), offsets.ctypes.data_as(u64p),
        lengths.ctypes.data_as(u64p), len(offsets),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), total,
        ctypes.byref(written))
    _check(rc, "read_batch")
    res, pos = [], 0
    for ln in lengths:
        res.append(out[pos:pos + int(ln)].tobytes())
        pos += int(ln)
    return res


# --------------------------------------------------------------------------
# Native fused JPEG decode (src/jpeg_decode.cc): decode + scaled IDCT +
# crop + mirror + normalize in ONE C pass — the reference's
# iter_image_recordio_2.cc ParseChunk role (libjpeg-turbo scaled decode).
# --------------------------------------------------------------------------

_JPEG_SRC = os.path.join(os.path.dirname(_SRC), "jpeg_decode.cc")
_jpeg_lib = None
_jpeg_tried = False


def _bind_jpeg(path):
    lib = ctypes.CDLL(path)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    f32p = ctypes.POINTER(ctypes.c_float)
    i32p = ctypes.POINTER(ctypes.c_int)
    lib.jpg_dims.argtypes = [u8p, ctypes.c_uint64, i32p, i32p]
    lib.jpg_dims.restype = ctypes.c_int
    lib.jpg_decode_crop_norm.argtypes = [
        u8p, ctypes.c_uint64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, f32p, f32p, f32p]
    lib.jpg_decode_crop_norm.restype = ctypes.c_int
    return lib


def jpeg_lib():
    """The bound native jpeg decoder, building on first use; None when
    unavailable (no toolchain / no libjpeg / MXNET_USE_NATIVE=0)."""
    global _jpeg_lib, _jpeg_tried
    if _jpeg_lib is not None or _jpeg_tried:
        return _jpeg_lib
    with _lock:
        if _jpeg_lib is not None or _jpeg_tried:
            return _jpeg_lib
        _jpeg_tried = True
        if not config.get_int("MXNET_USE_NATIVE", 1):
            return None
        _jpeg_lib = _load("libjpegdec.so", _JPEG_SRC, _bind_jpeg,
                          extra_link=("-ljpeg",))
        return _jpeg_lib


def jpeg_decode_available():
    return jpeg_lib() is not None


def jpeg_dims(buf):
    """(width, height) from the JPEG header without decoding, or None."""
    lib = jpeg_lib()
    if lib is None:
        return None
    arr = _np.frombuffer(buf, _np.uint8)
    w, h = ctypes.c_int(), ctypes.c_int()
    rc = lib.jpg_dims(arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                      len(arr), ctypes.byref(w), ctypes.byref(h))
    if rc != 0:
        return None
    return w.value, h.value


def jpeg_decode_crop_norm(buf, crop_hw, crop_xy=None, mirror=False,
                          min_side=0, mean=(0.0, 0.0, 0.0),
                          std=(1.0, 1.0, 1.0), out=None):
    """Fused decode+crop+normalize -> float32 CHW ndarray (or writes into
    ``out``).  Returns None when the native decoder is unavailable or the
    (possibly IDCT-scaled) image cannot cover the crop — the caller falls
    back to its generic decode+resize path."""
    lib = jpeg_lib()
    if lib is None:
        return None
    h, w = crop_hw
    arr = _np.frombuffer(buf, _np.uint8)
    if out is None:
        out = _np.empty((3, h, w), _np.float32)
    mean_a = _np.ascontiguousarray(mean, _np.float32)
    stdi_a = 1.0 / _np.ascontiguousarray(std, _np.float32)
    x, y = (-1, -1) if crop_xy is None else (int(crop_xy[0]),
                                             int(crop_xy[1]))
    f32p = ctypes.POINTER(ctypes.c_float)
    rc = lib.jpg_decode_crop_norm(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(arr),
        w, h, x, y, int(bool(mirror)), int(min_side),
        mean_a.ctypes.data_as(f32p), stdi_a.ctypes.data_as(f32p),
        out.ctypes.data_as(f32p))
    if rc != 0:
        return None
    return out
