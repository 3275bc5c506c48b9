"""ctypes bindings for the native host runtime (``csrc/host_ops.cpp``).

The reference ships apex_C (``csrc/flatten_unflatten.cpp``) as a C++
extension built by setup.py with graceful degradation when absent
(``apex/parallel/distributed.py:13-33`` falls back to torch's python
path). Same contract here: the shared library is compiled on first use
with g++ (no pip involved), cached next to this file, and every entry
point has a numpy fallback — ``available`` tells you which path is live.

The cached library is keyed on a hash of the source: its file name is
``_libapex_tpu_host.<hash>.so``, so a library built from another version
of ``host_ops.cpp`` (``*.so`` is gitignored, and a copied checkout can
carry one) is never loaded; it is rebuilt and the stale file removed.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import tempfile
from typing import List, Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_HERE), "csrc", "host_ops.cpp")
_LIB_PREFIX = "_libapex_tpu_host"

_lib: Optional[ctypes.CDLL] = None
available = False
jpeg_available = False
_ABI = 2


def _lib_path(src: str = _SRC, lib_dir: str = _HERE) -> str:
    """Where the library built from ``src`` as it is now lives."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(lib_dir, f"{_LIB_PREFIX}.{digest}.so")


def _build(src: str, lib_path: str) -> bool:
    lib_dir = os.path.dirname(lib_path)
    try:
        # build into a temp file then atomic-rename so concurrent imports
        # never load a half-written .so
        fd, tmp = tempfile.mkstemp(suffix=".so.tmp", dir=lib_dir)
        os.close(fd)
        base = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
                src, "-o", tmp]
        # try with libjpeg (the batch decode path) first; fall back to a
        # decode-less build on systems without it
        r = subprocess.run(base + ["-DAPEX_HAVE_JPEG", "-ljpeg"],
                           capture_output=True, timeout=120)
        if r.returncode != 0:
            r = subprocess.run(base, capture_output=True, timeout=120)
        if r.returncode != 0:
            os.unlink(tmp)
            return False
        os.replace(tmp, lib_path)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def _open_or_build(src: str = _SRC,
                   lib_dir: str = _HERE) -> Optional[ctypes.CDLL]:
    """The library for ``src``'s current contents: the cached one when
    its hash matches, else a fresh build (libraries of other hashes in
    ``lib_dir`` are removed).  None when it cannot be built or does not
    load here — callers then take the numpy paths."""
    lib_path = _lib_path(src, lib_dir)
    for stale in glob.glob(os.path.join(lib_dir, _LIB_PREFIX + "*.so")):
        if stale != lib_path:
            try:
                os.unlink(stale)
            except OSError:
                pass
    if not os.path.exists(lib_path) and not _build(src, lib_path):
        return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:          # e.g. built on another architecture
        return None
    lib.apex_native_abi_version.restype = ctypes.c_int
    if lib.apex_native_abi_version() != _ABI:
        return None          # source and bindings disagree
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib, available, jpeg_available
    if _lib is not None:
        return _lib
    if os.environ.get("APEX_TPU_NO_NATIVE"):
        # build-matrix hook: force the python-only install path (the
        # reference's "no --cpp_ext" axis) without monkeypatching
        return None
    lib = _open_or_build()
    if lib is None:
        return None

    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.apex_gather_rows.argtypes = [u8p, ctypes.c_int64, i64p,
                                     ctypes.c_int64, u8p, ctypes.c_int]
    lib.apex_flatten.argtypes = [ctypes.POINTER(u8p), i64p, ctypes.c_int64,
                                 u8p, ctypes.c_int]
    lib.apex_unflatten.argtypes = [u8p, ctypes.POINTER(u8p), i64p,
                                   ctypes.c_int64, ctypes.c_int]
    lib.apex_normalize_u8.argtypes = [u8p, ctypes.c_int64, ctypes.c_int64,
                                      f32p, f32p, f32p, ctypes.c_int]
    lib.apex_decode_jpeg_batch.restype = ctypes.c_int64
    lib.apex_decode_jpeg_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int64, ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(ctypes.c_uint64), u8p, u8p,
        ctypes.c_int]
    lib.apex_jpeg_available.restype = ctypes.c_int
    _lib = lib
    available = True
    jpeg_available = bool(lib.apex_jpeg_available())
    return lib


def _u8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def gather_rows(src: np.ndarray, idx: np.ndarray, *,
                n_threads: int = 0) -> np.ndarray:
    """``out[i] = src[idx[i]]`` along axis 0, multi-threaded memcpy.

    Contiguous ``src`` of any dtype; ``idx`` int64. Falls back to numpy
    fancy indexing when the native library is unavailable.
    """
    lib = _load()
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(idx, np.int64)
    if lib is None:
        return src[idx]
    out = np.empty((idx.shape[0],) + src.shape[1:], src.dtype)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    lib.apex_gather_rows(
        _u8(src), row_bytes,
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        idx.shape[0], _u8(out), n_threads)
    return out


def flatten(arrays: List[np.ndarray], *, n_threads: int = 0) -> np.ndarray:
    """Pack host arrays into one flat byte-compatible 1-D array of the
    common dtype (apex_C ``flatten`` analog; reference
    ``csrc/flatten_unflatten.cpp:5-10``)."""
    if not arrays:
        return np.empty((0,), np.float32)
    dtype = arrays[0].dtype
    if any(a.dtype != dtype for a in arrays):
        raise ValueError("flatten requires a uniform dtype across arrays")
    arrays = [np.ascontiguousarray(a) for a in arrays]
    lib = _load()
    if lib is None:
        return np.concatenate([a.reshape(-1) for a in arrays])
    total = sum(a.size for a in arrays)
    out = np.empty((total,), dtype)
    n = len(arrays)
    srcs = (ctypes.POINTER(ctypes.c_uint8) * n)(*[_u8(a) for a in arrays])
    sizes = (ctypes.c_int64 * n)(*[a.nbytes for a in arrays])
    lib.apex_flatten(srcs, ctypes.cast(sizes, ctypes.POINTER(ctypes.c_int64)),
                     n, _u8(out), n_threads)
    return out


def unflatten(flat: np.ndarray, like: List[np.ndarray], *,
              n_threads: int = 0) -> List[np.ndarray]:
    """Split ``flat`` back into arrays shaped like ``like`` (apex_C
    ``unflatten`` analog; reference ``csrc/flatten_unflatten.cpp:12-17``)."""
    flat = np.ascontiguousarray(flat)
    total = sum(a.size for a in like)
    if flat.size != total:
        raise ValueError(f"flat has {flat.size} elems; expected {total}")
    lib = _load()
    if lib is None:
        outs, off = [], 0
        for a in like:
            outs.append(flat[off:off + a.size].reshape(a.shape).astype(
                a.dtype, copy=True))
            off += a.size
        return outs
    outs = [np.empty(a.shape, flat.dtype) for a in like]
    n = len(like)
    dsts = (ctypes.POINTER(ctypes.c_uint8) * n)(*[_u8(o) for o in outs])
    sizes = (ctypes.c_int64 * n)(*[o.nbytes for o in outs])
    lib.apex_unflatten(_u8(flat), dsts,
                       ctypes.cast(sizes, ctypes.POINTER(ctypes.c_int64)),
                       n, n_threads)
    return outs


def normalize_u8(x: np.ndarray, mean, std, *, n_threads: int = 0) -> np.ndarray:
    """uint8 NHWC -> fp32 ``(x - mean[c]) / std[c]`` fused on the host
    (the imagenet pipeline's normalize step; falls back to numpy)."""
    x = np.ascontiguousarray(x, np.uint8)
    c = x.shape[-1]
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    lib = _load()
    if lib is None:
        return (x.astype(np.float32) - mean) / std
    out = np.empty(x.shape, np.float32)
    lib.apex_normalize_u8(
        _u8(x), x.size // c, c,
        mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n_threads)
    return out


def decode_jpeg_batch(paths: List[str], image_size: int, *,
                      train: bool = False, seeds=None,
                      out: Optional[np.ndarray] = None,
                      n_threads: int = 0):
    """Decode + transform a batch of JPEG files into uint8 NHWC — one
    GIL-free native call, one thread per image (libjpeg-turbo decode,
    DCT-scaled, transform fused; ``csrc/host_ops.cpp``).

    ``train`` fuses RandomResizedCrop(0.08-1.0)+hflip (per-image
    ``seeds``); eval fuses Resize(short=size*256/224)+CenterCrop — the
    reference's torchvision transforms
    (``examples/imagenet/main_amp.py:218-236``).

    Returns ``(batch, fail)``: ``fail[i]`` is True for files the native
    path could not decode (missing/corrupt/CMYK/non-JPEG) — those slots
    are untouched; the caller decodes them with its fallback (PIL).
    Raises RuntimeError when the native library/libjpeg is unavailable —
    callers gate on :data:`jpeg_available`.
    """
    lib = _load()
    if lib is None or not jpeg_available:
        raise RuntimeError("native JPEG decode unavailable "
                           "(check apex_tpu.ops.native.jpeg_available)")
    n = len(paths)
    if out is None:
        out = np.empty((n, image_size, image_size, 3), np.uint8)
    if out.shape != (n, image_size, image_size, 3) or \
            out.dtype != np.uint8 or not out.flags.c_contiguous:
        # a bad buffer here means native threads writing out of bounds
        raise ValueError(
            f"out must be C-contiguous uint8 of shape "
            f"{(n, image_size, image_size, 3)}; got {out.dtype} "
            f"{out.shape} contiguous={out.flags.c_contiguous}")
    fail = np.zeros((n,), np.uint8)
    if seeds is None:
        if train:
            # seed 0 for every image would silently freeze the
            # augmentation RNG across images AND epochs
            raise ValueError(
                "decode_jpeg_batch(train=True) requires per-image seeds")
        seeds = np.zeros((n,), np.uint64)
    seeds = np.ascontiguousarray(seeds, np.uint64)
    cpaths = (ctypes.c_char_p * n)(*[os.fsencode(p) for p in paths])
    lib.apex_decode_jpeg_batch(
        cpaths, n, image_size, int(train),
        seeds.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        _u8(out), _u8(fail), n_threads)
    return out, fail.astype(bool)


# trigger a build eagerly so `available` reflects reality at import time,
# mirroring the reference's import-time extension probe
# (apex/multi_tensor_apply/multi_tensor_apply.py:8-14)
_load()
