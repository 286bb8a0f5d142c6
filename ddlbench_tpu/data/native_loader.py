"""ctypes binding for the native data pipeline (native/dataloader.cpp):
on-disk raw-tensor dataset factory + mmap-backed prefetching batch loader.

This is the real-data path behind the CLI's ``-s`` flag (the reference stages
random JPEGs and torch-DataLoader-reads them back,
benchmark/generate_synthetic_data.py); the default benchmark path remains
device-side PRNG synthesis (data/synthetic.py). Datasets are stored as
``images.bin`` (N*H*W*C uint8) + ``labels.bin`` (N int32) + ``meta.json``.
"""

from __future__ import annotations

import ctypes
import json
import os
import subprocess
from typing import Iterator, Optional, Tuple

import numpy as np

from ddlbench_tpu.config import DatasetSpec

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdataloader.so")

_lib = None
_lib_failed = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    try:
        # Always run make (an incremental no-op when current): the .so is
        # not tracked by git, so an existing one may be stale against the
        # committed dataloader.cpp and would be called with the wrong ABI.
        subprocess.run(["make", "-C", _NATIVE_DIR, "-s"], check=True,
                       capture_output=True, timeout=120)
        lib = ctypes.CDLL(_LIB_PATH)
        lib.dataset_generate.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int64, ctypes.c_uint64, ctypes.c_int,
        ]
        lib.dataset_generate.restype = ctypes.c_int
        lib.loader_open.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int64, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_int, ctypes.c_int,
        ]
        lib.loader_open.restype = ctypes.c_void_p
        lib.loader_next.argtypes = [
            ctypes.c_void_p,
            np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ]
        lib.loader_next.restype = ctypes.c_int
        lib.loader_destroy.argtypes = [ctypes.c_void_p]
        lib.loader_destroy.restype = None
        _lib = lib
    except Exception:
        _lib_failed = True
    return _lib


def available() -> bool:
    return _load() is not None


def generate_dataset(data_dir: str, spec: DatasetSpec, split: str = "train",
                     count: Optional[int] = None, seed: int = 1,
                     threads: int = 4) -> str:
    """Write a raw synthetic dataset for one split; returns its directory.

    generate_synthetic_data.py parity: same blueprint sizes by default, raw
    uint8 tensors instead of JPEGs (no decode cost on a benchmark that never
    looks at the pixels).
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native dataloader unavailable (no toolchain?)")
    count = count or (spec.train_size if split == "train" else spec.test_size)
    if spec.kind in ("tokens", "seq2seq"):
        # token sequences ride the same raw-uint8 store: one sample is T+1
        # tokens x 4 little-endian bytes (viewed as int32 % vocab on read;
        # the +1 gives the next-token label shift, data/synthetic.py:90-95;
        # seq2seq's source-position masking happens at read time in ondisk.py)
        h, w, c = spec.seq_len + 1, 4, 1
    else:
        h, w, c = spec.image_size
    out = os.path.join(data_dir, spec.name, split)
    os.makedirs(out, exist_ok=True)
    rc = lib.dataset_generate(out.encode(), h, w, c, spec.num_classes,
                              count, seed, threads)
    if rc != 0:
        raise RuntimeError(f"dataset_generate failed rc={rc}")
    with open(os.path.join(out, "meta.json"), "w") as f:
        json.dump({"h": h, "w": w, "c": c, "classes": spec.num_classes,
                   "count": count, "seed": seed, "kind": spec.kind}, f)
    return out


class NativeDataLoader:
    """Prefetching batch iterator over a generated dataset directory."""

    def __init__(self, dataset_dir: str, batch_size: int, seed: int = 1,
                 shuffle: bool = True, ring_depth: int = 4,
                 prefetch_depth: int = 2):
        lib = _load()
        if lib is None:
            raise RuntimeError("native dataloader unavailable")
        with open(os.path.join(dataset_dir, "meta.json")) as f:
            meta = json.load(f)
        self.meta = meta
        self.batch_size = batch_size
        self._lib = lib
        self._handle = lib.loader_open(
            dataset_dir.encode(), meta["h"], meta["w"], meta["c"],
            meta["classes"], meta["count"], batch_size, seed,
            int(shuffle), ring_depth,
        )
        if not self._handle:
            raise RuntimeError(f"loader_open failed for {dataset_dir}")
        # Rotating ring of preallocated buffer pairs: next() hands out a
        # pair WITHOUT copying (the old implementation memcpy'd both
        # buffers per call). THE SAFETY INVARIANT IS THE CONSUMER'S
        # BARRIER, NOT THE RING SIZE: data/ondisk.py fully consumes every
        # batch before requesting the next one — synchronous numpy
        # arithmetic on the token path, a device_get execution barrier on
        # the jitted normalize/augment image path — so even a 2-buffer
        # ring would be safe, and no ring size alone would be (jax can
        # zero-copy alias an aligned host buffer, leaving nothing a
        # lifetime window could protect). The prefetch_depth+1 sizing just
        # keeps a grace window for that contract's documented lifetime.
        nbuf = max(2, prefetch_depth + 1)
        self._bufs = [
            (np.empty((batch_size, meta["h"], meta["w"], meta["c"]), np.uint8),
             np.empty((batch_size,), np.int32))
            for _ in range(nbuf)
        ]
        self._buf_i = 0

    @property
    def steps_per_epoch(self) -> int:
        return self.meta["count"] // self.batch_size

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        """Return the next (images, labels) batch.

        The arrays are views into a rotating ring of ``max(2,
        prefetch_depth + 1)`` preallocated pairs: a returned batch stays
        valid for ``ring_size - 1`` further ``next()`` calls and is
        overwritten by the ``ring_size``-th.
        FULLY consume (or copy) a batch before calling ``next()`` again —
        a jax array built from these views may zero-copy alias them, so
        deferring consumption to any later point is unsafe regardless of
        the ring size (see data/ondisk.py's execution barrier)."""
        img_buf, lbl_buf = self._bufs[self._buf_i]
        self._buf_i = (self._buf_i + 1) % len(self._bufs)
        rc = self._lib.loader_next(self._handle, img_buf.reshape(-1),
                                   lbl_buf)
        if rc != 0:
            raise RuntimeError(f"loader_next rc={rc}")
        return img_buf, lbl_buf

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.loader_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
