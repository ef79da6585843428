"""Sharded on-disk datasets with a native loading path.

Reference: the reference's data plane is Spark — partitioned datasets live
in HDFS/parquet and are read by the JVM's native IO machinery, far from the
Python heap (reference: distkeras/trainers.py trains from a DataFrame the
executors stream in). This module is the TPU rebuild's equivalent: a
dataset too big for one host array lives as **shards on disk** and streams
through training with native-code loading.

Format (one directory):

- ``meta.json`` — columns, dtypes, shapes, per-shard row counts;
- ``shard_{i:05d}.{column}.bin`` — raw C-order array bytes per column.

The loading path uses ``native/libdk_dataio.so`` via ctypes (built on
demand like the transport lib): positional file reads and batch-assembly
kernels run with the GIL released, so a Python prefetch thread overlaps
shard IO + shuffled batch gather + (optionally) a fused float32→bfloat16
cast with the device step dispatch. Everything falls back to numpy when no
compiler exists.
"""

from __future__ import annotations

import ctypes
import json
import os
import queue
import threading
import warnings
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from distkeras_tpu.data.dataset import PartitionedDataset
from distkeras_tpu.utils import native

_native = None
_native_tried = False


def _load_native():
    """The ctypes shard-IO library, (re)built when it does not match its
    ``.c`` source (native/build.py · ensure_lib); None — with a warning
    — when it cannot be built, and numpy does the reads and gathers."""
    global _native, _native_tried
    if _native is not None or _native_tried:
        return _native
    _native_tried = True
    try:
        path = native.ensure_lib("libdk_dataio.so")
    except native.BuildError as e:
        warnings.warn(
            "native shard IO unavailable, using the numpy paths: "
            f"{type(e).__name__}: {e}", RuntimeWarning, stacklevel=2)
        return None
    lib = ctypes.CDLL(path)
    lib.dk_pread.restype = ctypes.c_int
    lib.dk_pread.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_void_p,
    ]
    lib.dk_gather_rows.restype = None
    lib.dk_gather_rows.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p,
    ]
    lib.dk_gather_cast_f32_bf16.restype = None
    lib.dk_gather_cast_f32_bf16.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p,
    ]
    lib.dk_cast_f32_bf16.restype = None
    lib.dk_cast_f32_bf16.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
    ]
    _native = lib
    return lib


def cast_f32_bf16(x: np.ndarray) -> np.ndarray:
    """Contiguous float32 → bfloat16 via the native RNE kernel (bit-exact
    with XLA's cast); numpy/ml_dtypes fallback without the library."""
    import ml_dtypes

    lib = _load_native()
    if lib is None or x.size == 0:
        return x.astype(ml_dtypes.bfloat16)
    x = np.ascontiguousarray(x, np.float32)
    out = np.empty(x.shape, ml_dtypes.bfloat16)
    lib.dk_cast_f32_bf16(
        x.ctypes.data_as(ctypes.c_void_p), x.size,
        out.ctypes.data_as(ctypes.c_void_p),
    )
    return out


def native_dataio_active() -> bool:
    return _load_native() is not None


# -- writing -----------------------------------------------------------------


def write_shards(
    dataset: PartitionedDataset, directory: str,
    rows_per_shard: Optional[int] = None,
) -> str:
    """Write a PartitionedDataset as a shard directory (one shard per
    partition by default, or re-split to ``rows_per_shard``)."""
    if rows_per_shard is not None:
        n = dataset.num_rows
        dataset = dataset.repartition(max(1, -(-n // rows_per_shard)))
    os.makedirs(directory, exist_ok=True)
    columns = dataset.columns
    meta: Dict = {"version": 1, "columns": {}, "shards": []}
    for c in columns:
        first = dataset.partition(0)[c]
        meta["columns"][c] = {
            "dtype": np.asarray(first).dtype.str,
            "row_shape": list(np.asarray(first).shape[1:]),
        }
    for i in range(dataset.num_partitions):
        part = dataset.partition(i)
        if sorted(part) != sorted(columns):
            raise ValueError(
                f"partition {i} columns {sorted(part)} != partition 0's "
                f"{sorted(columns)} — extra columns would be dropped and "
                "missing ones leave holes in the shard files"
            )
        rows = len(next(iter(part.values())))
        meta["shards"].append({"rows": rows})
        for c in columns:
            want_dtype = np.dtype(meta["columns"][c]["dtype"])
            want_shape = tuple(meta["columns"][c]["row_shape"])
            arr = np.ascontiguousarray(part[c])
            if arr.shape[1:] != want_shape:
                raise ValueError(
                    f"partition {i} column '{c}': row shape {arr.shape[1:]} "
                    f"!= partition 0's {want_shape}"
                )
            if arr.dtype != want_dtype:
                # same-kind casts keep the file consistent with meta.json —
                # but same_kind permits lossy integer narrowing and float
                # overflow-to-inf, so value-check anything not float→float
                if not np.can_cast(arr.dtype, want_dtype, casting="same_kind"):
                    raise ValueError(
                        f"partition {i} column '{c}': dtype {arr.dtype} is "
                        f"incompatible with partition 0's {want_dtype}"
                    )
                cast = arr.astype(want_dtype)
                if arr.dtype.kind in "iu" and want_dtype.kind in "iu":
                    # range check, not round-trip: signed↔unsigned wrap is
                    # bijective, so a round-trip would pass on wrapped data
                    info = np.iinfo(want_dtype)
                    if arr.size and not (
                        info.min <= int(arr.min())
                        and int(arr.max()) <= info.max
                    ):
                        raise ValueError(
                            f"partition {i} column '{c}': values do not "
                            f"survive the {arr.dtype}→{want_dtype} cast"
                        )
                elif want_dtype.kind in "iu" or arr.dtype.kind in "iu":
                    if not np.array_equal(cast.astype(arr.dtype), arr):
                        raise ValueError(
                            f"partition {i} column '{c}': values do not "
                            f"survive the {arr.dtype}→{want_dtype} cast"
                        )
                elif not np.all(np.isfinite(cast) == np.isfinite(arr)):
                    raise ValueError(
                        f"partition {i} column '{c}': {arr.dtype}→"
                        f"{want_dtype} overflows to inf"
                    )
                arr = cast
            arr.tofile(os.path.join(directory, f"shard_{i:05d}.{c}.bin"))
    with open(os.path.join(directory, "meta.json"), "w") as fh:
        json.dump(meta, fh)
    return directory


# -- reading -----------------------------------------------------------------


class ShardedDataset:
    """Lazy reader over a shard directory.

    ``load()`` materializes everything into a PartitionedDataset (small
    data); ``batches()`` streams shuffled fixed-shape batches with a
    background prefetch thread (big data) — the path whose IO/assembly
    runs in native code with the GIL released.
    """

    def __init__(self, directory: str):
        self.directory = directory
        with open(os.path.join(directory, "meta.json")) as fh:
            self.meta = json.load(fh)
        self.columns = sorted(self.meta["columns"])
        self.shard_rows = [s["rows"] for s in self.meta["shards"]]

    @property
    def num_shards(self) -> int:
        return len(self.shard_rows)

    @property
    def num_rows(self) -> int:
        return sum(self.shard_rows)

    def _col_info(self, c) -> Tuple[np.dtype, Tuple[int, ...]]:
        info = self.meta["columns"][c]
        return np.dtype(info["dtype"]), tuple(info["row_shape"])

    def read_shard(self, i: int) -> Dict[str, np.ndarray]:
        """One shard as {column: array}, via native pread when available."""
        out = {}
        lib = _load_native()
        for c in self.columns:
            dtype, row_shape = self._col_info(c)
            rows = self.shard_rows[i]
            shape = (rows,) + row_shape
            path = os.path.join(self.directory, f"shard_{i:05d}.{c}.bin")
            nbytes = int(np.prod(shape)) * dtype.itemsize
            if lib is not None:
                buf = np.empty(shape, dtype)
                rc = lib.dk_pread(
                    path.encode(), 0, nbytes,
                    buf.ctypes.data_as(ctypes.c_void_p),
                )
                if rc != 0:
                    raise IOError(f"dk_pread failed for {path}")
                out[c] = buf
            else:
                out[c] = np.fromfile(path, dtype=dtype).reshape(shape)
        return out

    def load(self) -> PartitionedDataset:
        """Materialize all shards (shard boundaries = partitions)."""
        return PartitionedDataset(
            [self.read_shard(i) for i in range(self.num_shards)]
        )

    # -- streaming batches with native assembly --------------------------

    def _gather(self, arr: np.ndarray, idx: np.ndarray, cast_bf16: bool):
        """Shuffled batch assembly: native row gather (+ fused f32→bf16)."""
        lib = _load_native()
        rows = len(idx)
        row_shape = arr.shape[1:]
        row_elems = int(np.prod(row_shape)) if row_shape else 1
        if lib is None or row_elems == 0:
            # numpy path; also zero-width rows (nothing for C to copy —
            # passing row_bytes=0 to memcpy loops is pointless and an
            # `or 1` default would read out of bounds)
            out = arr[idx]
            if cast_bf16 and arr.dtype == np.float32:
                import ml_dtypes

                out = out.astype(ml_dtypes.bfloat16)
            return out
        idx = np.ascontiguousarray(idx, np.int64)
        if cast_bf16 and arr.dtype == np.float32:
            import ml_dtypes

            out = np.empty((rows,) + row_shape, ml_dtypes.bfloat16)
            lib.dk_gather_cast_f32_bf16(
                arr.ctypes.data_as(ctypes.c_void_p), row_elems,
                idx.ctypes.data_as(ctypes.c_void_p), rows,
                out.ctypes.data_as(ctypes.c_void_p),
            )
            return out
        out = np.empty((rows,) + row_shape, arr.dtype)
        lib.dk_gather_rows(
            arr.ctypes.data_as(ctypes.c_void_p),
            row_elems * arr.dtype.itemsize,
            idx.ctypes.data_as(ctypes.c_void_p), rows,
            out.ctypes.data_as(ctypes.c_void_p),
        )
        return out

    def batches(
        self,
        batch_size: int,
        shuffle_seed: Optional[int] = None,
        cast_bf16: Optional[List[str]] = None,
        prefetch: int = 2,
        drop_remainder: bool = True,
        shards: Optional[Sequence[int]] = None,
    ) -> Iterator[Dict[str, np.ndarray]]:
        """Stream fixed-shape batches shard by shard.

        Shuffle is two-level, the standard big-data scheme Spark users
        know: shard order is shuffled globally, rows are shuffled within
        each shard (no global materialization). ``cast_bf16`` lists
        float32 columns to cast during assembly (fused in C). A background
        thread prefetches ``prefetch`` batches ahead; IO and assembly run
        GIL-released, overlapping the consumer's device dispatch.

        ``shards`` restricts the stream to a subset of shard indices —
        the hook multi-process trainers use to give each process a
        disjoint slice of the directory (shuffle then permutes within
        the subset only).
        """
        cast_cols = set(cast_bf16 or ())
        rng = (np.random.default_rng(shuffle_seed)
               if shuffle_seed is not None else None)
        shard_order = (np.asarray(list(shards), dtype=np.int64)
                       if shards is not None
                       else np.arange(self.num_shards))
        if rng is not None:
            rng.shuffle(shard_order)

        q: "queue.Queue" = queue.Queue(maxsize=max(1, prefetch))
        _END = object()
        stop = threading.Event()
        error: List[BaseException] = []

        def put(item) -> bool:
            """Bounded put that aborts when the consumer is gone — the
            producer must never block forever on an abandoned generator."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            try:
                leftover: Optional[Dict[str, np.ndarray]] = None
                for si in shard_order:
                    if stop.is_set():
                        return
                    shard = self.read_shard(int(si))
                    if leftover is not None:
                        shard = {
                            c: np.concatenate([leftover[c], shard[c]])
                            for c in self.columns
                        }
                        leftover = None
                    rows = len(next(iter(shard.values())))
                    idx = np.arange(rows)
                    if rng is not None:
                        rng.shuffle(idx)
                    n_full = rows // batch_size
                    for b in range(n_full):
                        bidx = idx[b * batch_size:(b + 1) * batch_size]
                        if not put({
                            c: self._gather(shard[c], bidx, c in cast_cols)
                            for c in self.columns
                        }):
                            return
                    tail = idx[n_full * batch_size:]
                    if len(tail):
                        leftover = {c: shard[c][tail] for c in self.columns}
                if leftover is not None and not drop_remainder:
                    # the remainder goes through the same assembly path as
                    # every other batch (casts applied, dtypes consistent)
                    n = len(next(iter(leftover.values())))
                    ridx = np.arange(n)
                    put({
                        c: self._gather(leftover[c], ridx, c in cast_cols)
                        for c in self.columns
                    })
            except BaseException as e:  # surfaced to the consumer
                error.append(e)
            finally:
                put(_END)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is _END:
                    break
                yield item
        finally:
            stop.set()
            # unblock a producer waiting on a full queue; its timed put
            # then observes stop and exits — no _END required after stop
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=10)
        if error:
            raise error[0]


class ShardRowSource:
    """grain ``RandomAccessDataSource`` view of a shard directory.

    SURVEY.md §7 notes grain is the environment's input library; this
    adapter lets a shard directory feed grain's samplers/DataLoaders
    (``grain.MapDataset.source(ShardRowSource(dir))``) without loading
    everything: rows resolve through a one-shard LRU so sequential and
    shard-local access patterns hit memory, and cold reads go through the
    native loader.
    """

    def __init__(self, directory_or_dataset, cache_shards: int = 2):
        self._sd = (directory_or_dataset
                    if isinstance(directory_or_dataset, ShardedDataset)
                    else ShardedDataset(directory_or_dataset))
        self._starts = np.cumsum([0] + self._sd.shard_rows)
        self._cache: "Dict[int, Dict[str, np.ndarray]]" = {}
        self._cache_order: List[int] = []
        self._cache_shards = max(1, cache_shards)
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return self._sd.num_rows

    def _shard_for(self, index: int) -> Tuple[int, int]:
        si = int(np.searchsorted(self._starts, index, side="right")) - 1
        return si, index - int(self._starts[si])

    def __getitem__(self, index: int) -> Dict[str, np.ndarray]:
        if index < 0:
            index += len(self)
        if not 0 <= index < len(self):
            raise IndexError(index)
        si, offset = self._shard_for(index)
        with self._lock:
            shard = self._cache.get(si)
        if shard is None:
            shard = self._sd.read_shard(si)
            with self._lock:
                self._cache[si] = shard
                self._cache_order.append(si)
                while len(self._cache_order) > self._cache_shards:
                    self._cache.pop(self._cache_order.pop(0), None)
        return {c: shard[c][offset] for c in self._sd.columns}


def map_shards(dataset: ShardedDataset, fn, out_directory: str) -> str:
    """Apply ``fn(shard_dict) -> shard_dict`` shard by shard, writing the
    results as a new shard directory — one shard resident at a time, so
    pipeline stages (transformers, predictors) run at disk scale exactly
    like the reference's ``mapPartitions`` stages ran on Spark partitions.
    """
    os.makedirs(out_directory, exist_ok=True)
    meta: Dict = {"version": 1, "columns": None, "shards": []}
    for i in range(dataset.num_shards):
        out = fn(dataset.read_shard(i))
        rows = {len(v) for v in out.values()}
        if len(rows) != 1:
            raise ValueError(
                f"map_shards fn returned ragged columns for shard {i}: "
                f"{ {k: len(v) for k, v in out.items()} }"
            )
        if meta["columns"] is None:
            meta["columns"] = {
                c: {
                    "dtype": np.asarray(v).dtype.str,
                    "row_shape": list(np.asarray(v).shape[1:]),
                }
                for c, v in out.items()
            }
        elif sorted(out) != sorted(meta["columns"]):
            raise ValueError(
                f"map_shards fn returned columns {sorted(out)} for shard "
                f"{i}, but shard 0 produced {sorted(meta['columns'])}"
            )
        meta["shards"].append({"rows": rows.pop()})
        for c, v in out.items():
            arr = np.ascontiguousarray(v)
            want = meta["columns"][c]
            if arr.dtype.str != want["dtype"] or \
                    list(arr.shape[1:]) != want["row_shape"]:
                raise ValueError(
                    f"map_shards fn output for shard {i} column '{c}' is "
                    f"{arr.dtype.str}/{list(arr.shape[1:])}, but shard 0 "
                    f"produced {want['dtype']}/{want['row_shape']}"
                )
            arr.tofile(
                os.path.join(out_directory, f"shard_{i:05d}.{c}.bin")
            )
    with open(os.path.join(out_directory, "meta.json"), "w") as fh:
        json.dump(meta, fh)
    return out_directory
