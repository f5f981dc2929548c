"""Build + bind the native (C++) host-side data kernels.

The reference leans on native code for its input pipeline without showing
any: torchvision transforms and the DataLoader worker pool are C++ under
the hood (singlegpu.py:154-180).  This module is the framework's explicit
equivalent: a small C++ OpenMP kernel (_native/crop_flip.cpp) compiled on
first use with the system toolchain and bound via ctypes — no pybind11 /
Python.h dependency.

The Python side draws all randomness (data/augment.py) and passes the
offsets in, so the native path is bit-identical to the numpy path and can
be swapped freely; ``DDP_TPU_NATIVE=0`` disables it.  A failed build is not
fatal (the .so is a throughput optimisation, not a semantic dependency) but
it is said once on stderr with the compiler's message, and the trainer's
start-up line carries ``native_augment=on|off`` (``n/a`` for a run that
augments on device and never builds it).  The .so is keyed by the
hash of its source and built inside the checkout (utils/platform.py), so
a machine that discards the home directory still finds it.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile
from typing import Optional

import numpy as np

from ..utils.platform import NATIVE_BUILD_DIR

_SRC = os.path.join(os.path.dirname(__file__), "_native", "crop_flip.cpp")
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build_and_load() -> ctypes.CDLL:
    with open(_SRC, "rb") as f:
        src = f.read()
    tag = hashlib.sha256(src).hexdigest()[:16]
    so_path = os.path.join(NATIVE_BUILD_DIR, f"crop_flip_{tag}.so")
    if not os.path.exists(so_path):
        os.makedirs(NATIVE_BUILD_DIR, exist_ok=True)
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=NATIVE_BUILD_DIR)
        os.close(fd)
        base = ["g++", "-O3", "-shared", "-fPIC", _SRC, "-o", tmp]
        for extra in (["-fopenmp"], []):  # OpenMP if available
            try:
                subprocess.run(base[:-2] + extra + base[-2:], check=True,
                               capture_output=True, timeout=120)
                break
            except subprocess.CalledProcessError as e:
                why = e.stderr.decode(errors="replace").strip()
            except (subprocess.TimeoutExpired, FileNotFoundError) as e:
                why = str(e)
        else:
            os.unlink(tmp)
            raise OSError(why)
        os.replace(tmp, so_path)  # atomic: concurrent builders race safely
    lib = ctypes.CDLL(so_path)
    lib.crop_flip_u8.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int64]
    lib.crop_flip_u8.restype = None
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded kernel library, building it on first call; None if the
    build failed (said once on stderr) or ``DDP_TPU_NATIVE=0``."""
    global _lib, _tried
    if not _tried:
        _tried = True
        if os.environ.get("DDP_TPU_NATIVE", "1") != "0":
            try:
                _lib = _build_and_load()
            except OSError as e:
                print("native augment kernel unavailable, host augmentation "
                      f"runs in numpy: {e}", file=sys.stderr)
    return _lib


def crop_flip(batch: np.ndarray, ys: np.ndarray, xs: np.ndarray,
              flip: np.ndarray) -> Optional[np.ndarray]:
    """Native RandomCrop+HFlip; None when the library is unavailable."""
    lib = get_lib()
    if lib is None or batch.dtype != np.uint8:
        # Non-uint8 batches (the numpy path handles any dtype) must not be
        # silently truncated by the u8 kernel — fall through to numpy.
        return None
    batch = np.ascontiguousarray(batch)
    ys = np.ascontiguousarray(ys, dtype=np.int64)
    xs = np.ascontiguousarray(xs, dtype=np.int64)
    flip_u8 = np.ascontiguousarray(flip, dtype=np.uint8)
    out = np.empty_like(batch)
    lib.crop_flip_u8(batch.ctypes.data, out.ctypes.data, ys.ctypes.data,
                     xs.ctypes.data, flip_u8.ctypes.data,
                     np.int64(batch.shape[0]))
    return out
