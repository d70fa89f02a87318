"""ctypes bindings for the native C++ runtime components.

Reference native inventory (SURVEY.md §2.12): MKL/MKL-DNN/BigQuant JNI are
absorbed by XLA; what remains native here is (a) the CRC32C/TFRecord codec
(≙ java/netty/Crc32c.java + visualization/tensorboard/RecordWriter.scala +
utils/tf/TFRecordIterator.scala) and (b) the multithreaded IO staging
reader (≙ the Engine "io" thread pool feeding input pipelines).

The shared library is built on demand from ``native/`` with g++. In a
checkout ``make`` decides: it rebuilds a library older than its sources, and
where it fails the library counts as absent — a stale one is never loaded.
Every entry point has a pure-Python implementation of the same semantics for
hosts with no toolchain; ``native_available()`` says which one is in use.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_LIB_NAME = "libbigdl_native.so"
_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))
_lock = threading.Lock()
_lib = None
_tried = False


def _build() -> bool:
    """Bring the library up to date with its sources. In a checkout
    (``native/Makefile`` present) that is ``make``'s verdict; an
    installed package has no sources and ships the library its wheel
    built."""
    makefile = os.path.join(_REPO, "native", "Makefile")
    if not os.path.exists(makefile):
        return os.path.exists(os.path.join(_HERE, _LIB_NAME))
    try:
        subprocess.run(["make", "-C", os.path.join(_REPO, "native")],
                       check=True, capture_output=True, timeout=120)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def get_lib():
    """The loaded native library, building it if needed; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        path = os.path.join(_HERE, _LIB_NAME)
        # make is a no-op when the .so is newer than the sources and
        # rebuilds one that is not; where it cannot, the library is
        # absent — never a stale one bound under new Python code
        if not _build():
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        lib.bigdl_crc32c.restype = ctypes.c_uint32
        lib.bigdl_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.bigdl_masked_crc32c.restype = ctypes.c_uint32
        lib.bigdl_masked_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.bigdl_tfrecord_frame.restype = ctypes.c_uint64
        lib.bigdl_tfrecord_frame.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p]
        lib.bigdl_loader_create.restype = ctypes.c_void_p
        lib.bigdl_loader_create.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.bigdl_loader_submit.restype = ctypes.c_int64
        lib.bigdl_loader_submit.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64]
        lib.bigdl_loader_next.restype = ctypes.c_int64
        lib.bigdl_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int)]
        lib.bigdl_loader_free.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.bigdl_loader_destroy.argtypes = [ctypes.c_void_p]
        try:  # absent from .so files built before augment.cc existed
            lib.bigdl_fused_augment.restype = None
            lib.bigdl_fused_augment.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,  # h, w, c
                ctypes.c_int64, ctypes.c_int64,                  # top, left
                ctypes.c_int64, ctypes.c_int64,                  # ch, cw
                ctypes.c_int,                                    # flip
                ctypes.POINTER(ctypes.c_float),                  # mean
                ctypes.POINTER(ctypes.c_float),                  # 1/std
                ctypes.POINTER(ctypes.c_float)]                  # out
        except AttributeError:
            pass
        _lib = lib
        return _lib


def native_available() -> bool:
    return get_lib() is not None


# ---------------------------------------------------------------- crc32c
_CRC_TABLE = None


def _py_table():
    global _CRC_TABLE
    if _CRC_TABLE is None:
        poly = 0x82F63B78
        tbl = []
        for i in range(256):
            crc = i
            for _ in range(8):
                crc = (crc >> 1) ^ (poly if crc & 1 else 0)
            tbl.append(crc)
        _CRC_TABLE = tbl
    return _CRC_TABLE


def crc32c(data: bytes) -> int:
    lib = get_lib()
    if lib is not None:
        return lib.bigdl_crc32c(data, len(data))
    tbl = _py_table()
    crc = 0xFFFFFFFF
    for b in data:
        crc = (crc >> 8) ^ tbl[(crc ^ b) & 0xFF]
    return crc ^ 0xFFFFFFFF


def masked_crc32c(data: bytes) -> int:
    lib = get_lib()
    if lib is not None:
        return lib.bigdl_masked_crc32c(data, len(data))
    crc = crc32c(data)
    return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


# --------------------------------------------------------------- tfrecord
import struct as _struct


def tfrecord_frame(payload: bytes) -> bytes:
    """Frame one TFRecord: len u64le | masked_crc(len) | data | masked_crc(data)."""
    lib = get_lib()
    if lib is not None:
        out = ctypes.create_string_buffer(len(payload) + 16)
        n = lib.bigdl_tfrecord_frame(payload, len(payload), out)
        return out.raw[:n]
    header = _struct.pack("<Q", len(payload))
    return (header + _struct.pack("<I", masked_crc32c(header)) + payload +
            _struct.pack("<I", masked_crc32c(payload)))


def tfrecord_iter(data: bytes):
    """Yield payloads from a concatenation of framed records
    (≙ utils/tf/TFRecordIterator.scala)."""
    off = 0
    n = len(data)
    while off + 12 <= n:
        (length,) = _struct.unpack_from("<Q", data, off)
        (lcrc,) = _struct.unpack_from("<I", data, off + 8)
        if masked_crc32c(data[off:off + 8]) != lcrc:
            raise ValueError(f"tfrecord length crc mismatch at {off}")
        if off + 16 + length > n:
            raise ValueError("truncated tfrecord")
        payload = data[off + 12: off + 12 + length]
        (dcrc,) = _struct.unpack_from("<I", data, off + 12 + length)
        if masked_crc32c(payload) != dcrc:
            raise ValueError(f"tfrecord data crc mismatch at {off}")
        yield payload
        off += 16 + length


# ------------------------------------------------------------ data loader
class PrefetchReader:
    """Ordered multithreaded byte-range reader backed by the C++ pool;
    falls back to synchronous Python reads when the library is absent."""

    def __init__(self, n_threads: int = 4, capacity: int = 32):
        self._lib = get_lib()
        self._handle = (self._lib.bigdl_loader_create(n_threads, capacity)
                        if self._lib is not None else None)
        self._py_queue = []

    def submit(self, path: str, offset: int = 0, length: int = 0) -> int:
        if self._handle is not None:
            return self._lib.bigdl_loader_submit(
                self._handle, path.encode(), offset, length)
        self._py_queue.append((path, offset, length))
        return len(self._py_queue) - 1

    def next(self) -> bytes:
        """Next completed read, in submission order. Raises IOError on a
        failed read, IndexError when nothing is outstanding."""
        if self._handle is not None:
            data = ctypes.POINTER(ctypes.c_uint8)()
            length = ctypes.c_uint64()
            err = ctypes.c_int()
            jid = self._lib.bigdl_loader_next(
                self._handle, ctypes.byref(data), ctypes.byref(length),
                ctypes.byref(err))
            if jid < 0:
                raise IndexError("no outstanding reads")
            try:
                if err.value != 0:
                    raise IOError(f"native read failed (code {err.value})")
                return ctypes.string_at(data, length.value)
            finally:
                self._lib.bigdl_loader_free(self._handle, jid)
        if not self._py_queue:
            raise IndexError("no outstanding reads")
        path, offset, length = self._py_queue.pop(0)
        with open(path, "rb") as f:
            if offset:
                f.seek(offset)
            return f.read(length) if length else f.read()

    def close(self):
        if self._handle is not None:
            self._lib.bigdl_loader_destroy(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------- fused augment
def fused_augment_available() -> bool:
    lib = get_lib()
    return lib is not None and hasattr(lib, "bigdl_fused_augment") \
        and getattr(lib.bigdl_fused_augment, "argtypes", None) is not None


def fused_augment(img, top: int, left: int, crop_h: int, crop_w: int,
                  flip: bool, means, inv_stds):
    """One-pass native crop+flip+normalize: (h, w, c) uint8 C-contiguous
    -> (crop_h, crop_w, c) float32. Returns None when the native kernel
    is unavailable or the input does not qualify (caller falls back to
    the composed numpy ops) — including an out-of-bounds crop window or
    means/inv_stds whose length differs from c: the C kernel trusts its
    arguments and would read past the buffers for a bad caller."""
    import numpy as np

    lib = get_lib()
    if not fused_augment_available():
        return None
    if (img.dtype != np.uint8 or img.ndim != 3
            or not img.flags.c_contiguous):
        return None
    h, w, c = img.shape
    mean = np.ascontiguousarray(means, np.float32)
    inv = np.ascontiguousarray(inv_stds, np.float32)
    if mean.shape != (c,) or inv.shape != (c,):
        return None
    if not (0 <= top and 0 <= left and crop_h >= 1 and crop_w >= 1
            and top + crop_h <= h and left + crop_w <= w):
        return None
    out = np.empty((crop_h, crop_w, c), np.float32)
    lib.bigdl_fused_augment(
        img.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        h, w, c, top, left, crop_h, crop_w, int(bool(flip)),
        mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        inv.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    return out
