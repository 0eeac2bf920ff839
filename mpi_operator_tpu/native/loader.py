"""ctypes binding for the native npy-shard loader (npy_loader.cc).

The shared library is built on first use with the system g++ (no pybind11
in the image — the C ABI + ctypes is the sanctioned binding path) and
cached next to the source, with the SHA-256 of the source it was built
from beside it; a library that is absent, or was not built from the
npy_loader.cc now on disk, is rebuilt (file times say nothing after a
checkout or a tree copy). Neither file is tracked by git. Everything
degrades gracefully: if no compiler
is available, `native_available()` is False and data/imagefolder.py keeps
its pure-Python feeder.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "npy_loader.cc")
_SO = os.path.join(_HERE, "libnpyloader.so")
_STAMP = _SO + ".sha256"
_lock = threading.Lock()
_lib = None
_build_error: Optional[str] = None


def _build() -> Optional[str]:
    """Compile the .so unless one built from the current source is there;
    returns an error string or None."""
    try:
        with open(_SRC, "rb") as fh:
            digest = hashlib.sha256(fh.read()).hexdigest()
        if os.path.exists(_SO) and os.path.exists(_STAMP):
            with open(_STAMP) as fh:
                if fh.read().strip() == digest:
                    return None
        proc = subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-pthread", _SRC, "-o",
             _SO + ".tmp"],
            capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            return f"g++ failed: {proc.stderr[-500:]}"
        os.replace(_SO + ".tmp", _SO)
        with open(_STAMP + ".tmp", "w") as fh:
            fh.write(digest + "\n")
        os.replace(_STAMP + ".tmp", _STAMP)
        return None
    except FileNotFoundError:
        return "g++ not found"
    except Exception as e:  # noqa: BLE001
        return f"build error: {e!r}"


def _load():
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        _build_error = _build()
        if _build_error is not None:
            return None
        lib = ctypes.CDLL(_SO)
        lib.nsl_open.restype = ctypes.c_void_p
        lib.nsl_open.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_int, ctypes.c_long, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_uint,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.c_char_p, ctypes.c_int]
        lib.nsl_next.restype = ctypes.c_int
        lib.nsl_next.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_char_p, ctypes.c_int]
        lib.nsl_close.restype = None
        lib.nsl_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


class NativeShardLoader:
    """Iterator of (images, labels) numpy batches produced by the C++
    loader: normalization + dtype conversion + shard IO run in a native
    prefetch thread, outside the GIL.

    images: [B, H, W, C] in `dtype` (float32 or bfloat16, already
    (x-mean)/std normalized); labels: [B] int32.
    """

    def __init__(self, shards: Sequence[Tuple[str, str]], batch_size: int,
                 image_shape: Tuple[int, int, int], dtype="float32",
                 mean: Sequence[float] = (127.5, 127.5, 127.5),
                 std: Sequence[float] = (127.5, 127.5, 127.5),
                 seed: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native loader unavailable: {_build_error}")
        self._lib = lib
        H, W, C = image_shape
        self.batch_size = batch_size
        self.image_shape = image_shape
        import ml_dtypes
        if np.dtype(dtype) == np.dtype(ml_dtypes.bfloat16) \
                or str(dtype) == "bfloat16":
            self._np_dtype = np.dtype(ml_dtypes.bfloat16)
        elif np.dtype(dtype) == np.float32:
            self._np_dtype = np.dtype(np.float32)
        else:
            # the Python feeder casts to whatever dtype was asked; the
            # native path only emits f32/bf16 — reject rather than let the
            # two paths silently produce different input dtypes
            raise ValueError(
                f"native loader emits float32 or bfloat16, not {dtype!r}")
        bf16 = self._np_dtype != np.float32
        img_paths = (ctypes.c_char_p * len(shards))(
            *[s[0].encode() for s in shards])
        lbl_paths = (ctypes.c_char_p * len(shards))(
            *[s[1].encode() for s in shards])
        mean_c = (ctypes.c_float * 3)(*[float(m) for m in mean])
        std_c = (ctypes.c_float * 3)(*[float(s) for s in std])
        err = ctypes.create_string_buffer(512)
        self._handle = lib.nsl_open(
            img_paths, lbl_paths, len(shards), batch_size, H, W, C,
            1 if bf16 else 0, seed & 0xFFFFFFFF, mean_c, std_c, err, 512)
        if not self._handle:
            raise RuntimeError(f"native loader: {err.value.decode()}")
        self._img = np.empty((batch_size, H, W, C), self._np_dtype)
        self._lbl = np.empty((batch_size,), np.int32)

    def __iter__(self):
        return self

    def __next__(self) -> Tuple[np.ndarray, np.ndarray]:
        err = ctypes.create_string_buffer(512)
        rc = self._lib.nsl_next(
            self._handle, self._img.ctypes.data_as(ctypes.c_void_p),
            self._lbl.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            err, 512)
        if rc != 0:
            raise RuntimeError(f"native loader: {err.value.decode()}")
        # copies so the caller may hold batches across iterations
        return self._img.copy(), self._lbl.copy()

    def close(self):
        if getattr(self, "_handle", None):
            self._lib.nsl_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


__all__ = ["NativeShardLoader", "native_available"]
