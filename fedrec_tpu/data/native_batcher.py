"""ctypes binding for the native C++ data engine (``native/fedrec_data.cpp``).

``NativeTrainBatcher`` is a drop-in replacement for
``fedrec_tpu.data.batcher.TrainBatcher`` whose host-side hot loop — epoch
shuffling, round-robin client sharding, negative sampling, batch packing —
runs in the C++ library (threaded for whole-epoch fills). This is the
TPU-native equivalent of the reference's torch ``DataLoader`` workers
(reference ``dataset.py:69-86``, ``main.py:166``): the reference's native
loading lives inside the torch wheel; ours is a first-class framework
component.

Shapes, sharding, padding, and pool-shorter-than-ratio semantics match the
Python batcher exactly; the negative-sampling RNG is the engine's own
deterministic per-(seed, epoch, client, batch) stream, so draws are
reproducible but not bit-identical to numpy's.

The shared library is loaded from ``native/libfedrec_data.so``; if missing,
``ensure_built()`` compiles it with ``make`` (g++ is part of the toolchain).
``is_available()`` gates use so pure-Python environments keep working.
"""

from __future__ import annotations

import ctypes
import subprocess
from pathlib import Path
from typing import Iterator

import numpy as np

from fedrec_tpu.data.batcher import Batch, IndexedSamples

_NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"
_LIB_PATH = _NATIVE_DIR / "libfedrec_data.so"

_lib: ctypes.CDLL | None = None
_load_error: str | None = None


def _is_stale() -> bool:
    """True when the library is older than the source or the Makefile it
    was built from (git does not carry the binary, a copied tree may)."""
    built = _LIB_PATH.stat().st_mtime
    return any(
        src.exists() and src.stat().st_mtime > built
        for src in (_NATIVE_DIR / "fedrec_data.cpp", _NATIVE_DIR / "Makefile")
    )


def ensure_built() -> bool:
    """Build the shared library if it is missing or older than its sources.
    Returns True when an up-to-date library is present.

    A failed build is cached (``_load_error``) so repeated availability
    probes don't re-spawn ``make`` each time.
    """
    global _load_error
    if _LIB_PATH.exists() and not _is_stale():
        return True
    if _load_error is not None:
        return False
    if not (_NATIVE_DIR / "Makefile").exists():
        _load_error = f"{_NATIVE_DIR}/Makefile missing"
        return False
    try:
        # -B: a Makefile newer than the library must rebuild it too
        subprocess.run(
            ["make", "-B", "-C", str(_NATIVE_DIR), "libfedrec_data.so"],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except (subprocess.SubprocessError, OSError) as e:
        _load_error = f"native build failed: {e}"
        return False
    if not _LIB_PATH.exists():
        _load_error = f"build succeeded but {_LIB_PATH} missing"
        return False
    return True


def _load() -> ctypes.CDLL | None:
    global _lib, _load_error
    if _lib is not None:
        return _lib
    if _load_error is not None:
        return None
    if not ensure_built():
        return None
    try:
        lib = ctypes.CDLL(str(_LIB_PATH))
    except OSError as e:  # pragma: no cover - host-specific
        _load_error = str(e)
        return None

    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.frd_create.restype = ctypes.c_void_p
    lib.frd_create.argtypes = [
        i32p, i32p, i32p, i32p, i32p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
    ]
    lib.frd_destroy.restype = None
    lib.frd_destroy.argtypes = [ctypes.c_void_p]
    lib.frd_num_batches.restype = ctypes.c_int64
    lib.frd_num_batches.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.frd_fill_batch.restype = ctypes.c_int
    lib.frd_fill_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        i32p, i32p, i32p, i32p,
    ]
    lib.frd_fill_epoch.restype = ctypes.c_int
    lib.frd_fill_epoch.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        i32p, i32p, i32p, i32p,
    ]
    _lib = lib
    return _lib


def is_available() -> bool:
    return _load() is not None


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


class NativeTrainBatcher:
    """TrainBatcher-compatible façade over the C++ engine."""

    def __init__(
        self,
        indexed: IndexedSamples,
        batch_size: int,
        npratio: int = 4,
        shuffle: bool = True,
        drop_remainder: bool = True,
        seed: int = 0,
        num_threads: int = 0,
    ):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native data engine unavailable: {_load_error}")
        self._lib = lib
        self.batch_size = batch_size
        self.npratio = npratio
        self.num_threads = num_threads
        self.max_his = indexed.history.shape[1]
        self.drop_remainder = drop_remainder
        self._n = len(indexed)

        pos = np.ascontiguousarray(indexed.pos, dtype=np.int32)
        pools = np.ascontiguousarray(indexed.neg_pools, dtype=np.int32)
        lens = np.ascontiguousarray(indexed.neg_lens, dtype=np.int32)
        hist = np.ascontiguousarray(indexed.history, dtype=np.int32)
        hlen = np.ascontiguousarray(indexed.his_len, dtype=np.int32)
        self._handle = lib.frd_create(
            _ptr(pos), _ptr(pools), _ptr(lens), _ptr(hist), _ptr(hlen),
            len(indexed), pools.shape[1], self.max_his,
            batch_size, npratio, int(shuffle), int(drop_remainder),
            ctypes.c_uint64(seed & 0xFFFFFFFFFFFFFFFF).value,
        )
        if not self._handle:
            raise RuntimeError("frd_create rejected the arguments")

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.frd_destroy(handle)
            self._handle = None

    # ------------------------------------------------------------------
    def num_batches(self, n: int | None = None) -> int:
        """Batches per epoch for ``n`` samples (TrainBatcher contract:
        the argument is a SAMPLE count, defaulting to the dataset size)."""
        n = self._n if n is None else n
        if self.drop_remainder:
            return n // self.batch_size
        return -(-n // self.batch_size)

    def _steps(self, num_clients: int) -> int:
        """Steps per epoch when dealt round-robin over ``num_clients``."""
        return int(self._lib.frd_num_batches(self._handle, num_clients))

    def _alloc(self, num_clients: int, steps: int | None = None):
        lead = () if steps is None else (steps,)
        b, c, h = self.batch_size, 1 + self.npratio, self.max_his
        return (
            np.empty((*lead, num_clients, b, c), np.int32),
            np.empty((*lead, num_clients, b, h), np.int32),
            np.empty((*lead, num_clients, b), np.int32),
            np.empty((*lead, num_clients, b), np.int32),
        )

    def _fill_batch(self, epoch: int, idx: int, num_clients: int) -> Batch:
        cand, hist, hlen, labels = self._alloc(num_clients)
        rc = self._lib.frd_fill_batch(
            self._handle, epoch, idx, num_clients,
            _ptr(cand), _ptr(hist), _ptr(hlen), _ptr(labels),
        )
        if rc != 0:
            raise ValueError(f"frd_fill_batch failed (rc={rc})")
        return Batch(cand, hist, hlen, labels)

    # ------------------------------------------------------------------
    def epoch_batches(self, epoch: int = 0) -> Iterator[Batch]:
        for i in range(self._steps(1)):
            b = self._fill_batch(epoch, i, 1)
            yield Batch(b.candidates[0], b.history[0], b.his_len[0], b.labels[0])

    def epoch_batches_sharded(
        self, num_clients: int, epoch: int = 0
    ) -> Iterator[Batch]:
        for i in range(self._steps(num_clients)):
            yield self._fill_batch(epoch, i, num_clients)

    def epoch_arrays_sharded(self, num_clients: int, epoch: int = 0) -> Batch:
        """Whole epoch (steps, C, B, ...) filled by the threaded native path."""
        steps = self._steps(num_clients)
        if steps == 0:
            raise ValueError(
                "no batches: dataset smaller than num_clients*batch_size"
            )
        cand, hist, hlen, labels = self._alloc(num_clients, steps)
        rc = self._lib.frd_fill_epoch(
            self._handle, epoch, num_clients, self.num_threads,
            _ptr(cand), _ptr(hist), _ptr(hlen), _ptr(labels),
        )
        if rc != 0:
            raise ValueError(f"frd_fill_epoch failed (rc={rc})")
        return Batch(cand, hist, hlen, labels)
