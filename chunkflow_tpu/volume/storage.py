"""The storage plane: one backend interface, block-granular hot cache,
concurrent block I/O.

The reference pipeline's production claim (18 PB of output images,
PAPER.md) rests on the storage path keeping thousands of workers fed,
yet until this module every byte moved through one blocking
``read().result()`` in volume/precomputed.py. Three facts make that the
wrong shape at fleet scale:

1. **Storage is block-granular.** A precomputed/zarr/n5 volume is a
   key-value store of fixed-size blocks; a cutout is a *set* of block
   GETs, and a worker can keep the ones its neighbor tasks will ask for
   again.
2. **Task grids overlap.** Inference chunks carry halos, so neighboring
   tasks re-fetch the same boundary blocks from cold storage — on an
   overlapping grid most block reads are repeats of a neighbor's.
3. **Blocks are immutable in the write-once layout.** Aligned chunks
   never share a block (the write-conflict-avoidance contract,
   volume/precomputed.py), which is exactly what makes a host-side
   block cache safe to share across tasks in a worker.

This module therefore provides, for every array store the repo touches
(neuroglancer precomputed per mip, tensorstore zarr/n5 datasets in the
plugins, in-memory test/bench fixtures):

* :class:`StorageBackend` — the one async array interface
  (:class:`TensorStoreBackend` for real drivers, :class:`MemoryBackend`
  for fixtures) plus the sidecar/existence KV plane
  (:class:`FileKV` / :class:`TensorStoreKV`, :func:`open_kv`);
* :class:`BlockCache` — a bytes-bounded, thread-safe (GL010/locksmith
  clean) LRU of storage blocks, shared process-wide via
  :func:`shared_cache` so halo reads of already-fetched blocks hit host
  memory (the page/block-granularity idiom Ragged Paged Attention uses
  to keep serving occupancy high, PAPERS.md);
* :func:`blockwise_cutout` — a cutout through the block cache: the blocks
  the cache lacks go to the driver as one request over the box that
  covers them (a few boxes where cached blocks lie in between;
  :func:`read_concurrency`, an adaptive-scheduler knob, bounds the boxes
  in flight), and the cache is fed from slices of what comes back. The
  driver reads a box's blocks on threads of its own: a cutout costs this
  thread one wait, not one for every eight blocks with the interpreter
  lock to win back each time;
* :func:`blockwise_save` — the coalescing write path: block-aligned
  writes commit as concurrent per-block futures (no read-modify-write)
  and update the cache write-through; unaligned writes fall back to one
  driver-level RMW write and invalidate the covered blocks, so
  read-after-write through the cache stays correct either way.

Kill switches: ``CHUNKFLOW_STORAGE=serial`` restores the historical
single-read path bit-identically (:func:`storage_mode`);
``CHUNKFLOW_STORAGE_CACHE_MB=0`` disables the cache (every read goes to
storage). Telemetry (docs/storage.md, docs/observability.md): spans
``storage/read`` / ``storage/write``; counters ``storage/hits``,
``storage/misses``, ``storage/block_reads``, ``storage/read_requests``,
``storage/bytes_read``, ``storage/bytes_written``,
``storage/aligned_writes``, ``storage/unaligned_writes``,
``storage/evictions``; gauge ``storage/cache_bytes``.

Coherence note: the cache is per-worker and trusts the write-once block
layout — blocks observed all-zero (tensorstore's fill_missing rendering
of absent blocks) are deliberately NOT cached, so a halo read that races
a neighbor task's first write re-fetches fresh bytes instead of pinning
stale zeros (docs/storage.md "Invalidation semantics").
"""
from __future__ import annotations

import abc
import contextvars
import itertools
import os
import threading
import time
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from chunkflow_tpu.core import telemetry

__all__ = [
    "storage_mode", "cache_bytes_limit", "read_concurrency",
    "set_read_concurrency", "BlockCache", "shared_cache",
    "reset_shared_cache", "StorageBackend", "TensorStoreBackend",
    "MemoryBackend", "KVBackend", "FileKV", "MemoryKV", "TensorStoreKV",
    "open_kv", "KVArrayBackend",
    "blockwise_cutout", "blockwise_save", "serial_cutout", "GatherFuture",
]

_OFF_VALUES = ("serial", "0", "off", "false", "no")


def storage_mode() -> str:
    """``concurrent`` (default) or ``serial`` (``CHUNKFLOW_STORAGE=serial``
    kill switch: the historical one-blocking-read path, bit-identically).
    Re-read per call so tests and long-lived workers can flip it."""
    value = os.environ.get("CHUNKFLOW_STORAGE", "concurrent").lower()
    return "serial" if value in _OFF_VALUES else "concurrent"


def cache_bytes_limit() -> int:
    """Byte budget of the shared hot-block cache
    (``CHUNKFLOW_STORAGE_CACHE_MB``, default 256 MB; <=0 disables the
    cache entirely). A malformed value falls back to the default."""
    raw = os.environ.get("CHUNKFLOW_STORAGE_CACHE_MB", "")
    try:
        mb = float(raw) if raw else 256.0
    except ValueError:
        mb = 256.0
    return int(mb * (1 << 20))


# ---------------------------------------------------------------------------
# read-concurrency knob (adaptive-scheduler managed)
# ---------------------------------------------------------------------------
_CONC_LOCK = threading.Lock()
_READ_CONCURRENCY: Optional[int] = None


def read_concurrency() -> int:
    """Driver requests (boxes of missing blocks, :func:`blockwise_cutout`)
    a cutout keeps in flight: the ``CHUNKFLOW_STORAGE_CONCURRENCY``
    initial value (default 8), runtime adjustable via
    :func:`set_read_concurrency` — the adaptive scheduler's ``storage``
    depth knob widens it when ``scheduler/load`` dominates the stall
    breakdown (flow/scheduler.py). A cold cutout is one box, so the bound
    bites only where cached blocks cut the misses into many."""
    with _CONC_LOCK:
        if _READ_CONCURRENCY is not None:
            return _READ_CONCURRENCY
    raw = os.environ.get("CHUNKFLOW_STORAGE_CONCURRENCY", "")
    try:
        return max(1, int(raw)) if raw else 8
    except ValueError:
        return 8


def set_read_concurrency(n: int) -> None:
    """Set the live per-cutout bound on requests in flight
    (DepthController ``storage`` knob; tests)."""
    global _READ_CONCURRENCY
    with _CONC_LOCK:
        _READ_CONCURRENCY = max(1, int(n))
    telemetry.gauge("storage/read_concurrency", max(1, int(n)))


def _reset_read_concurrency() -> None:
    """Back to the env-resolved default (tests)."""
    global _READ_CONCURRENCY
    with _CONC_LOCK:
        _READ_CONCURRENCY = None


# ---------------------------------------------------------------------------
# block-granular hot-chunk LRU
# ---------------------------------------------------------------------------
class BlockCache:
    """Bytes-bounded, thread-safe LRU of immutable storage blocks.

    Keys are ``(backend.cache_token, block_lo)`` tuples; values are
    read-only ndarrays holding exactly one storage block (clamped to the
    dataset domain). All mutation sits behind one lock and nothing
    blocking ever runs under it (GL012); hit/miss/eviction totals are
    kept locally and exposed as attributes — the cutout/save paths fold
    them into the telemetry registry outside the lock."""

    def __init__(self, max_bytes: int):
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, np.ndarray]" = OrderedDict()
        self._nbytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def nbytes(self) -> int:
        with self._lock:
            return self._nbytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get(self, key) -> Optional[np.ndarray]:
        """The cached block (read-only view) or None; counts the
        hit/miss and refreshes recency."""
        with self._lock:
            arr = self._entries.get(key)
            if arr is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return arr

    def put(self, key, arr: np.ndarray) -> bool:
        """Insert one block (copied defensively only by callers; the
        cache marks it read-only in place). Oversized blocks are
        refused; inserting evicts LRU entries until the byte budget
        holds."""
        nbytes = int(arr.nbytes)
        if nbytes > self.max_bytes:
            return False
        arr.setflags(write=False)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._nbytes -= old.nbytes
            self._entries[key] = arr
            self._nbytes += nbytes
            while self._nbytes > self.max_bytes and self._entries:
                _, evicted = self._entries.popitem(last=False)
                self._nbytes -= evicted.nbytes
                self.evictions += 1
        return True

    def invalidate(self, key) -> bool:
        """Drop one block (write-path invalidation); True if present."""
        with self._lock:
            arr = self._entries.pop(key, None)
            if arr is None:
                return False
            self._nbytes -= arr.nbytes
            return True

    def invalidate_token(self, token) -> int:
        """Drop every block of one dataset (volume deleted/recreated);
        returns the number of entries removed."""
        with self._lock:
            doomed = [k for k in self._entries if k and k[0] == token]
            for key in doomed:
                self._nbytes -= self._entries.pop(key).nbytes
            return len(doomed)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._nbytes = 0


_CACHE_LOCK = threading.Lock()
_SHARED_CACHE: Optional[BlockCache] = None


def shared_cache() -> Optional[BlockCache]:
    """The process-wide hot-block cache shared across tasks in a worker
    (None when ``CHUNKFLOW_STORAGE_CACHE_MB<=0``). Rebuilt when the
    byte budget changes so tests can resize it via the env knob."""
    global _SHARED_CACHE
    limit = cache_bytes_limit()
    if limit <= 0:
        return None
    with _CACHE_LOCK:
        if _SHARED_CACHE is None or _SHARED_CACHE.max_bytes != limit:
            _SHARED_CACHE = BlockCache(limit)
        return _SHARED_CACHE


def reset_shared_cache() -> None:
    """Drop the shared cache (tests; a fresh one opens on next use)."""
    global _SHARED_CACHE
    with _CACHE_LOCK:
        _SHARED_CACHE = None


# ---------------------------------------------------------------------------
# futures
# ---------------------------------------------------------------------------
class GatherFuture:
    """One future over many: ``result()`` drains every member even when
    one fails (first exception wins — the drain_pending_writes
    contract), and ``.copy`` aggregates the members' copy legs so the
    ``save(wait=False)`` caller-may-reuse-the-buffer protocol holds for
    multi-block writes. Members without a ``.copy`` leg (plain
    concurrent.futures) count as copied once resolved."""

    __slots__ = ("_futures",)

    def __init__(self, futures: Iterable):
        self._futures = list(futures)

    def result(self):
        first: Optional[BaseException] = None
        for future in self._futures:
            try:
                future.result()
            except BaseException as exc:
                if first is None:
                    first = exc
        if first is not None:
            raise first
        return None

    def done(self) -> bool:
        return all(
            f.done() for f in self._futures if hasattr(f, "done")
        )

    @property
    def copy(self) -> "GatherFuture":
        return GatherFuture(
            [getattr(f, "copy", f) for f in self._futures]
        )


# ---------------------------------------------------------------------------
# the backend interface
# ---------------------------------------------------------------------------
class StorageBackend(abc.ABC):
    """Uniform async array-store interface: everything upstream
    (PrecomputedVolume mips, the tensorstore zarr/n5 plugins, test and
    bench fixtures) reads and writes through this, so the concurrent
    cutout/save machinery and the block cache are written once.

    Index space is the backend's native one (xyzc for precomputed,
    dataset order for zarr/n5, plain array axes for fixtures); the
    zyx-czyx facade stays where it always was, in
    volume/precomputed.py."""

    #: stable identity of the backing dataset — the cache key namespace
    cache_token: str

    @property
    @abc.abstractmethod
    def domain(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """(inclusive_min, exclusive_max) index bounds, native order."""

    @property
    @abc.abstractmethod
    def dtype(self) -> np.dtype:
        ...

    @property
    @abc.abstractmethod
    def block_shape(self) -> Tuple[int, ...]:
        """Storage block extent per dimension (native order)."""

    @property
    def grid_offset(self) -> Tuple[int, ...]:
        """Origin the block grid is anchored at (defaults to the domain
        lower bound — true for precomputed and zarr alike)."""
        return self.domain[0]

    @abc.abstractmethod
    def read_async(self, lo: Sequence[int], hi: Sequence[int]):
        """Start reading ``[lo, hi)``; returns a future of an ndarray."""

    @abc.abstractmethod
    def write_async(self, lo: Sequence[int], hi: Sequence[int], arr):
        """Start writing ``arr`` over ``[lo, hi)``; returns a future."""


class TensorStoreBackend(StorageBackend):
    """A :class:`StorageBackend` over one opened tensorstore dataset.

    Block shape defaults to the driver's read-chunk layout (the storage
    block for precomputed/zarr/n5), falling back to the whole domain
    when the driver reports none — a degenerate single-block grid that
    keeps the blockwise paths correct, if cache-coarse."""

    def __init__(self, store, token: Optional[str] = None,
                 block_shape: Optional[Sequence[int]] = None,
                 grid_offset: Optional[Sequence[int]] = None):
        self._store = store
        spec_token = token
        if spec_token is None:
            try:
                spec_token = str(store.spec(minimal_spec=True).to_json())
            except Exception:
                spec_token = f"tensorstore-{id(store)}"
        self.cache_token = spec_token
        lo = tuple(int(v) for v in store.domain.inclusive_min)
        hi = tuple(int(v) for v in store.domain.exclusive_max)
        self._domain = (lo, hi)
        if block_shape is None:
            block_shape = self._layout_block_shape(store, lo, hi)
        self._block_shape = tuple(int(v) for v in block_shape)
        self._grid_offset = (
            tuple(int(v) for v in grid_offset)
            if grid_offset is not None else lo
        )

    @staticmethod
    def _layout_block_shape(store, lo, hi):
        try:
            shape = store.chunk_layout.read_chunk.shape
        except Exception:
            shape = None
        if shape is None or any(not s for s in shape):
            return tuple(h - l for l, h in zip(lo, hi))
        return tuple(int(s) for s in shape)

    @classmethod
    def open(cls, spec: dict, token: Optional[str] = None,
             **kwargs) -> "TensorStoreBackend":
        import tensorstore as ts

        return cls(ts.open(spec).result(), token=token, **kwargs)

    @property
    def store(self):
        return self._store

    @property
    def domain(self):
        return self._domain

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self._store.dtype.numpy_dtype)

    @property
    def block_shape(self):
        return self._block_shape

    @property
    def grid_offset(self):
        return self._grid_offset

    def _slices(self, lo, hi):
        return tuple(slice(l, h) for l, h in zip(lo, hi))

    def read_async(self, lo, hi):
        return self._store[self._slices(lo, hi)].read()

    def write_async(self, lo, hi, arr):
        return self._store[self._slices(lo, hi)].write(arr)


class MemoryBackend(StorageBackend):
    """An in-memory :class:`StorageBackend` over a numpy array — the
    test fixture and the bench's cold-storage stand-in.

    ``latency_s`` charges a simulated per-BLOCK fetch latency (an object
    GET per storage block, how remote stores actually bill a cutout),
    slept inside a worker thread of the backend's pool. A read of
    ``[lo, hi)`` is charged the storage blocks it covers ``max_workers``
    at a time, which is how a driver reads a box: it fans the box out
    over its blocks under its own request limit (one block is one
    latency; a box of 741 blocks at 8 workers is 93). A write is charged
    its blocks one after the other."""

    _SEQ = itertools.count()

    def __init__(self, array: np.ndarray,
                 block_shape: Optional[Sequence[int]] = None,
                 latency_s: float = 0.0, max_workers: int = 8):
        from concurrent.futures import ThreadPoolExecutor

        self._array = array
        self._lock = threading.Lock()
        self._latency_s = float(latency_s)
        self._max_workers = int(max_workers)
        self.cache_token = f"memory-{next(self._SEQ)}"
        self._block_shape = tuple(
            int(v) for v in (block_shape or array.shape)
        )
        self._pool = ThreadPoolExecutor(
            max_workers=max_workers,
            thread_name_prefix="chunkflow-storage",
        )

    @property
    def domain(self):
        return (
            tuple(0 for _ in self._array.shape),
            tuple(int(s) for s in self._array.shape),
        )

    @property
    def dtype(self) -> np.dtype:
        return self._array.dtype

    @property
    def block_shape(self):
        return self._block_shape

    def _covered_blocks(self, lo, hi) -> int:
        n = 1
        for l, h, b in zip(lo, hi, self._block_shape):
            n *= max(1, -((-(h - (l - l % b))) // b))
        return n

    def _slices(self, lo, hi):
        return tuple(slice(l, h) for l, h in zip(lo, hi))

    def _read(self, lo, hi):
        if self._latency_s:
            # sleep OUTSIDE the lock (GL012): the latency is the remote
            # round-trip, not contention on the local buffer
            rounds = -(-self._covered_blocks(lo, hi) // self._max_workers)
            time.sleep(self._latency_s * rounds)
        with self._lock:
            return np.array(self._array[self._slices(lo, hi)], copy=True)

    def _write(self, lo, hi, arr):
        if self._latency_s:
            time.sleep(self._latency_s * self._covered_blocks(lo, hi))
        with self._lock:
            self._array[self._slices(lo, hi)] = arr

    def read_async(self, lo, hi):
        return self._pool.submit(self._read, tuple(lo), tuple(hi))

    def write_async(self, lo, hi, arr):
        return self._pool.submit(self._write, tuple(lo), tuple(hi), arr)

    def close(self) -> None:
        self._pool.shutdown(wait=False)


# ---------------------------------------------------------------------------
# the KV plane (sidecar files + block existence)
# ---------------------------------------------------------------------------
class KVBackend(abc.ABC):
    """Sidecar/object plane of a volume root: ``info`` and JSON
    sidecars, plus batched block-existence checks for resume skip
    logic. One handle per volume, opened once and cached
    (volume/precomputed.py) — not re-opened per call."""

    @abc.abstractmethod
    def read_bytes(self, name: str) -> Optional[bytes]:
        """Value of ``name`` or None when absent."""

    @abc.abstractmethod
    def write_bytes(self, name: str, data: bytes) -> None:
        ...

    @abc.abstractmethod
    def exists_many(self, names: Sequence[str]) -> Dict[str, bool]:
        """Batched stat-style existence of every name — never a full
        value download per key (the resume skip-logic path checks
        whole task grids through this)."""


class FileKV(KVBackend):
    """Local-filesystem KV plane (bare paths and file:// roots)."""

    def __init__(self, root: str):
        self.root = root

    def read_bytes(self, name: str) -> Optional[bytes]:
        path = os.path.join(self.root, name)
        if not os.path.exists(path):
            return None
        with open(path, "rb") as f:
            return f.read()

    def write_bytes(self, name: str, data: bytes) -> None:
        # tmp + rename: a concurrent reader (another worker assembling
        # an interface plane from face sidecars, or a replayed task
        # rewriting the same object) must never observe a torn value
        path = os.path.join(self.root, name)
        os.makedirs(os.path.dirname(path) or self.root, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)

    def exists_many(self, names: Sequence[str]) -> Dict[str, bool]:
        return {
            name: os.path.exists(os.path.join(self.root, name))
            for name in names
        }


class MemoryKV(KVBackend):
    """In-process KV plane (tests, the bench's sidecar stand-in).
    Thread-safe; values are immutable bytes so reads need no copies."""

    def __init__(self):
        self._lock = threading.Lock()
        self._data: Dict[str, bytes] = {}

    def read_bytes(self, name: str) -> Optional[bytes]:
        with self._lock:
            return self._data.get(name)

    def write_bytes(self, name: str, data: bytes) -> None:
        with self._lock:
            self._data[name] = bytes(data)

    def exists_many(self, names: Sequence[str]) -> Dict[str, bool]:
        with self._lock:
            return {name: name in self._data for name in names}


class TensorStoreKV(KVBackend):
    """Remote KV plane over one cached ``ts.KvStore`` handle.

    Existence checks are batched: one ``KvStore.list`` over the tight
    key range spanning the queried names (a single round trip listing
    only keys, no values) — never the historical per-name full-value
    ``read().result()`` download. Falls back to concurrent per-name
    reads if the driver cannot list."""

    def __init__(self, spec: dict):
        self.spec = dict(spec)
        # a kvstore path is a PREFIX to tensorstore: without a trailing
        # slash, "root" + "1_1_1/..." resolves to "root1_1_1/..." and
        # every name lookup silently misses (the array drivers append
        # the slash internally, which is why reads worked while the
        # seed's per-name existence probe never could)
        path = self.spec.get("path")
        if path and not path.endswith("/"):
            self.spec["path"] = path + "/"
        self._lock = threading.Lock()
        self._kv = None

    @property
    def kv(self):
        """The KvStore handle, opened once (satellite: no re-open per
        info/read_json/has_all_blocks call). Double-checked so the
        blocking driver open never runs under the lock; a lost race
        opens one redundant handle and drops it."""
        with self._lock:
            kv = self._kv
        if kv is None:
            import tensorstore as ts

            opened = ts.KvStore.open(self.spec).result()
            with self._lock:
                if self._kv is None:
                    self._kv = opened
                kv = self._kv
        return kv

    def read_bytes(self, name: str) -> Optional[bytes]:
        result = self.kv.read(name).result()
        if result.state == "missing":
            return None
        return bytes(result.value)

    def write_bytes(self, name: str, data: bytes) -> None:
        self.kv.write(name, data).result()

    def exists_many(self, names: Sequence[str]) -> Dict[str, bool]:
        if not names:
            return {}
        import tensorstore as ts

        ordered = sorted(names)
        try:
            keys = self.kv.list(
                ts.KvStore.KeyRange(
                    inclusive_min=ordered[0],
                    exclusive_max=ordered[-1] + "\x00",
                )
            ).result()
            present = {
                k.decode() if isinstance(k, bytes) else str(k)
                for k in keys
            }
            return {name: name in present for name in names}
        except Exception:
            # drivers without list support: concurrent reads (still one
            # wave in flight, not one blocking round trip per block)
            futures = [(name, self.kv.read(name)) for name in names]
            return {
                name: future.result().state != "missing"
                for name, future in futures
            }


def open_kv(spec: dict) -> KVBackend:
    """The right KV plane for a kvstore spec: direct filesystem access
    for the file driver, a cached tensorstore handle otherwise."""
    if spec.get("driver") == "file":
        return FileKV(spec["path"])
    return TensorStoreKV(spec)


class KVArrayBackend(StorageBackend):
    """A :class:`StorageBackend` persisting one npy object per storage
    block through any :class:`KVBackend` — the dependency-free shared
    array store of the segmentation plane (docs/segmentation.md): a
    FileKV root gives multi-process workers a common label volume with
    no tensorstore requirement, a :class:`MemoryKV` gives tests one.

    Blocks are keyed ``<prefix>/<lo..hi bbox string>.npy`` on the grid
    anchored at the domain origin; absent blocks read as ``fill``
    (labels default to background). Writes covering whole (clamped)
    blocks store them directly; partial writes read-modify-write the
    covered blocks — safe under the aligned-chunk contract (parallel
    writers never share a block), and the FileKV tmp+rename write keeps
    concurrent readers untorn either way."""

    _SEQ = itertools.count()

    def __init__(self, kv: KVBackend, domain, dtype,
                 block_shape: Sequence[int], prefix: str = "blocks",
                 fill=0, max_workers: int = 4):
        from concurrent.futures import ThreadPoolExecutor

        self._kv = kv
        lo, hi = domain
        self._domain = (
            tuple(int(v) for v in lo), tuple(int(v) for v in hi)
        )
        self._dtype = np.dtype(dtype)
        self._block_shape = tuple(int(v) for v in block_shape)
        self._prefix = prefix
        self._fill = fill
        root = getattr(kv, "root", None)
        self.cache_token = (
            f"kvarray:{root}:{prefix}" if root is not None
            else f"kvarray:mem{next(self._SEQ)}:{prefix}"
        )
        self._pool = ThreadPoolExecutor(
            max_workers=max_workers,
            thread_name_prefix="chunkflow-kvarray",
        )

    @property
    def domain(self):
        return self._domain

    @property
    def dtype(self) -> np.dtype:
        return self._dtype

    @property
    def block_shape(self):
        return self._block_shape

    def _block_key(self, blo, bhi) -> str:
        span = "_".join(f"{l}-{h}" for l, h in zip(blo, bhi))
        return f"{self._prefix}/{span}.npy"

    def _read_block(self, blo, bhi) -> np.ndarray:
        import io

        data = self._kv.read_bytes(self._block_key(blo, bhi))
        if data is None:
            return np.full(
                tuple(h - l for l, h in zip(blo, bhi)),
                self._fill, dtype=self._dtype,
            )
        with telemetry.span("storage/decode"):
            return np.load(io.BytesIO(data), allow_pickle=False)

    def _write_block(self, blo, bhi, arr: np.ndarray) -> None:
        import io

        with telemetry.span("storage/encode"):
            buf = io.BytesIO()
            np.save(buf, np.ascontiguousarray(arr, dtype=self._dtype),
                    allow_pickle=False)
        self._kv.write_bytes(self._block_key(blo, bhi), buf.getvalue())

    def _read(self, lo, hi) -> np.ndarray:
        out = np.empty(
            tuple(h - l for l, h in zip(lo, hi)), dtype=self._dtype
        )
        dlo, dhi = self._domain
        for blo, bhi in _covering_blocks(
            lo, hi, self._block_shape, self.grid_offset, dlo, dhi
        ):
            _copy_block(out, lo, hi, self._read_block(blo, bhi), blo, bhi)
        return out

    def _write(self, lo, hi, arr: np.ndarray) -> None:
        arr = np.asarray(arr)
        dlo, dhi = self._domain
        for blo, bhi in _covering_blocks(
            lo, hi, self._block_shape, self.grid_offset, dlo, dhi
        ):
            covers = all(
                l <= bl and bh <= h
                for l, h, bl, bh in zip(lo, hi, blo, bhi)
            )
            sel = tuple(
                slice(max(l, bl) - l, min(h, bh) - l)
                for l, h, bl, bh in zip(lo, hi, blo, bhi)
            )
            if covers:
                self._write_block(blo, bhi, arr[sel])
                continue
            block = self._read_block(blo, bhi)  # partial: RMW
            block[tuple(
                slice(max(l, bl) - bl, min(h, bh) - bl)
                for l, h, bl, bh in zip(lo, hi, blo, bhi)
            )] = arr[sel]
            self._write_block(blo, bhi, block)

    # the pool threads run under a copy of the caller's context, so the
    # storage/decode and storage/encode spans keep the caller's
    # storage/read or storage/write span as parent, and its task
    def read_async(self, lo, hi):
        return self._pool.submit(contextvars.copy_context().run,
                                 self._read, tuple(lo), tuple(hi))

    def write_async(self, lo, hi, arr):
        return self._pool.submit(contextvars.copy_context().run,
                                 self._write, tuple(lo), tuple(hi), arr)

    def close(self) -> None:
        self._pool.shutdown(wait=False)


_BACKEND_LOCK = threading.Lock()
_OPEN_BACKENDS: Dict[str, TensorStoreBackend] = {}


def open_backend_cached(spec: dict) -> TensorStoreBackend:
    """Open (once per process) a :class:`TensorStoreBackend` for a full
    tensorstore spec — the plugin path (load_tensorstore/load_n5) calls
    this per task, and re-opening the driver per call would defeat both
    the driver's own handle reuse and the block cache's token stability.
    The blocking driver open runs outside the lock; a lost race keeps
    the first-registered backend."""
    import json as _json

    key = _json.dumps(spec, sort_keys=True)
    with _BACKEND_LOCK:
        backend = _OPEN_BACKENDS.get(key)
    if backend is None:
        opened = TensorStoreBackend.open(spec, token=key)
        with _BACKEND_LOCK:
            backend = _OPEN_BACKENDS.setdefault(key, opened)
    return backend


def reset_open_backends() -> None:
    """Drop the plugin-path backend handles (tests)."""
    with _BACKEND_LOCK:
        _OPEN_BACKENDS.clear()


# ---------------------------------------------------------------------------
# blockwise concurrent reads
# ---------------------------------------------------------------------------
def _block_ranges(lo, hi, block, goff):
    """Per dimension, the grid indices (grid anchored at ``goff``) of the
    blocks that ``[lo, hi)`` touches."""
    return [
        range((lo[d] - goff[d]) // block[d],
              -((-(hi[d] - goff[d])) // block[d]))
        for d in range(len(lo))
    ]


def _grid_bounds(start, stop, block, goff, dlo, dhi):
    """Bounds ``(lo, hi)`` of the whole blocks with grid indices
    ``[start, stop)``, clamped to the domain."""
    ndim = len(start)
    return (
        tuple(max(goff[d] + start[d] * block[d], dlo[d])
              for d in range(ndim)),
        tuple(min(goff[d] + stop[d] * block[d], dhi[d])
              for d in range(ndim)),
    )


def _covering_blocks(lo, hi, block, goff, dlo, dhi):
    """Clamped block bounds ``(blo, bhi)`` covering ``[lo, hi)`` on the
    grid anchored at ``goff``, in grid order."""
    return [
        _grid_bounds(idx, [i + 1 for i in idx], block, goff, dlo, dhi)
        for idx in itertools.product(*_block_ranges(lo, hi, block, goff))
    ]


def _tile_boxes(mask: np.ndarray) -> List[Tuple[tuple, tuple]]:
    """Index boxes ``(start, stop)`` that tile exactly the True cells of
    ``mask``. Greedy in grid order: the first cell not yet covered opens
    a box, which grows along the last axis, then the one before, as long
    as the whole next slab is True and uncovered. A mask that is all
    True is one box; a False cell in the middle leaves the few boxes
    around it; no box ever holds a False cell."""
    mask = mask.copy()
    boxes = []
    for flat in np.flatnonzero(mask):
        start = tuple(int(i) for i in np.unravel_index(flat, mask.shape))
        if not mask[start]:
            continue  # an earlier box took it
        stop = [i + 1 for i in start]
        for d in reversed(range(mask.ndim)):
            while stop[d] < mask.shape[d]:
                slab = tuple(
                    slice(stop[d], stop[d] + 1) if k == d
                    else slice(start[k], stop[k])
                    for k in range(mask.ndim)
                )
                if not mask[slab].all():
                    break
                stop[d] += 1
        mask[tuple(slice(a, b) for a, b in zip(start, stop))] = False
        boxes.append((start, tuple(stop)))
    return boxes


def _box_blocks(arr: np.ndarray, box_lo, block, goff, dlo, dhi):
    """The blocks of a box the driver returned, ready for the cache:
    ``(blo, array)`` pairs, every array memory of its own (a view would
    keep the whole box alive), all-zero blocks left out.

    No numpy call is made per block. Each one gives the interpreter lock
    up and has to win it back from the worker's other threads, and a
    cutout has hundreds of blocks: per block ``.any()`` and a copy took
    48 ms a cutout of 741 blocks alone and 216-248 ms beside the threads
    a worker runs (the chip's host, PERF.md Findings PR 34); this takes
    40 ms either way. The box is cut, per axis, into runs of equally
    sized blocks (the whole ones, and the clamped one at the domain's
    edge; along the first axis a row of blocks at a time); each part is
    turned block-major by one copy and checked for zeros by one
    reduction, and a block is then a slice of its bytes."""
    ndim = arr.ndim
    box_hi = tuple(l + s for l, s in zip(box_lo, arr.shape))
    runs = []  # per axis: (offset in the box, block size, blocks)
    for d, indices in enumerate(_block_ranges(box_lo, box_hi, block, goff)):
        edges = [max(goff[d] + i * block[d], dlo[d]) for i in indices]
        edges.append(box_hi[d])
        axis, offset = [], 0
        for size, group in itertools.groupby(
                b - a for a, b in zip(edges, edges[1:])):
            count = len(list(group))
            axis.append((offset, size, count))
            offset += size * count
        runs.append(axis)
    # one row of blocks along the first axis at a time: the block-major
    # copy is then a few MB that the allocator hands back and forth, where
    # a copy of the whole box is fresh memory every cutout, and on the
    # chip's host the page faults of that cost more than the copying
    # (212 MB: 461 ms whole, 178 ms by rows; PERF.md Findings PR 34)
    runs[0] = [(o + i * s, s, 1) for o, s, n in runs[0] for i in range(n)]
    for part in itertools.product(*runs):
        counts = [n for _, _, n in part]
        shape = tuple(s for _, s, _ in part)
        # axes [n0, s0, n1, s1, ...] -> [n0, n1, ..., s0, s1, ...], in the
        # backend's own axis order (n: blocks, s: block size)
        split = arr[tuple(slice(o, o + s * n) for o, s, n in part)].reshape(
            [v for _, s, n in part for v in (n, s)])
        major = np.ascontiguousarray(split.transpose(
            list(range(0, 2 * ndim, 2)) + list(range(1, 2 * ndim, 2))
        )).reshape(int(np.prod(counts)), -1)
        nonzero = major.any(axis=1)
        raw = memoryview(major.view(np.uint8).reshape(-1))
        nbytes = major.shape[1] * major.itemsize
        for k, idx in enumerate(itertools.product(*map(range, counts))):
            if nonzero[k]:
                blo = tuple(
                    box_lo[d] + part[d][0] + idx[d] * part[d][1]
                    for d in range(ndim)
                )
                yield blo, np.frombuffer(
                    bytes(raw[k * nbytes:(k + 1) * nbytes]), dtype=arr.dtype
                ).reshape(shape)


def _copy_block(out, lo, hi, arr, blo, bhi) -> None:
    """Copy the ``[lo,hi)``-intersecting part of a block array (covering
    ``[blo,bhi)``) into the output array (origin ``lo``)."""
    sel_out, sel_blk = [], []
    for d in range(len(lo)):
        ilo = max(lo[d], blo[d])
        ihi = min(hi[d], bhi[d])
        sel_out.append(slice(ilo - lo[d], ihi - lo[d]))
        sel_blk.append(slice(ilo - blo[d], ihi - blo[d]))
    out[tuple(sel_out)] = arr[tuple(sel_blk)]


def _check_domain(backend: StorageBackend, lo, hi) -> None:
    dlo, dhi = backend.domain
    for d in range(len(lo)):
        if lo[d] < dlo[d] or hi[d] > dhi[d] or lo[d] >= hi[d]:
            raise ValueError(
                f"request [{tuple(lo)}, {tuple(hi)}) outside storage "
                f"domain [{dlo}, {dhi})"
            )


def serial_cutout(backend: StorageBackend, lo: Sequence[int],
                  hi: Sequence[int]) -> np.ndarray:
    """The historical path: one blocking whole-range read. Kept as the
    bit-identity reference for the concurrent path (tests, bench,
    ``CHUNKFLOW_STORAGE=serial``)."""
    lo, hi = tuple(int(v) for v in lo), tuple(int(v) for v in hi)
    _check_domain(backend, lo, hi)
    with telemetry.span("storage/read", mode="serial"):
        arr = np.asarray(backend.read_async(lo, hi).result())
    telemetry.inc("storage/bytes_read", arr.nbytes)
    return arr


def blockwise_cutout(backend: StorageBackend, lo: Sequence[int],
                     hi: Sequence[int],
                     cache: Optional[BlockCache] = None) -> np.ndarray:
    """Read ``[lo, hi)`` through the block cache: cached blocks are
    served from host memory, and the blocks the cache lacks go to the
    driver as ONE request over the box of whole (clamped) blocks that
    covers them, or the few boxes around cached blocks that lie in
    between (:func:`_tile_boxes`: from the blocks' coordinates alone). The
    driver fans a box out over its blocks on threads of its own, which
    never take the interpreter lock; this thread waits once a box, copies
    it into the result (or returns it, where the box is the request), and
    feeds the cache from it (:func:`_box_blocks`: copies, zeros left out,
    no numpy call a block). A box never covers a
    cached block, so what a cache hit supplied (a block written and not
    yet durable) is never overwritten by the driver's bytes.
    :func:`read_concurrency` bounds the boxes in flight. Whole blocks are
    read even at the request's edges: a neighbor task's halo read then
    hits the cache instead of cold storage."""
    lo, hi = tuple(int(v) for v in lo), tuple(int(v) for v in hi)
    _check_domain(backend, lo, hi)
    dlo, dhi = backend.domain
    block, goff = backend.block_shape, backend.grid_offset
    token = backend.cache_token
    ranges = _block_ranges(lo, hi, block, goff)
    blocks = _covering_blocks(lo, hi, block, goff, dlo, dhi)
    missing = np.ones(len(blocks), dtype=bool)
    hits = []
    bytes_read = 0
    with telemetry.span("storage/read", mode="blockwise",
                        blocks=len(blocks)) as span:
        if cache is not None:
            for k, bounds in enumerate(blocks):
                cached = cache.get((token, bounds[0]))
                if cached is not None:
                    missing[k] = False
                    hits.append((cached, bounds))
        first = [r.start for r in ranges]
        boxes = [
            _grid_bounds(
                [f + i for f, i in zip(first, start)],
                [f + i for f, i in zip(first, stop)],
                block, goff, dlo, dhi,
            )
            for start, stop in _tile_boxes(
                missing.reshape([len(r) for r in ranges]))
        ]
        span.annotate(requests=len(boxes))
        # a request on block bounds with nothing cached is its one box:
        # the driver's array is then the result, and is not copied
        whole = boxes == [(lo, hi)]
        out = None if whole else np.empty(
            tuple(h - l for l, h in zip(lo, hi)), dtype=backend.dtype
        )
        for cached, (blo, bhi) in hits:
            _copy_block(out, lo, hi, cached, blo, bhi)
        wave = max(1, read_concurrency())
        for i in range(0, len(boxes), wave):
            batch = boxes[i:i + wave]
            futures = [
                backend.read_async(box_lo, box_hi)
                for box_lo, box_hi in batch
            ]
            for (box_lo, box_hi), future in zip(batch, futures):
                arr = np.asarray(future.result())
                bytes_read += arr.nbytes
                if whole:
                    out = arr
                else:
                    _copy_block(out, lo, hi, arr, box_lo, box_hi)
                if cache is not None:
                    # all-zero blocks may simply not exist yet
                    # (fill_missing rendering) and are never pinned: a
                    # later read must see the neighbor's eventual write,
                    # not stale cached zeros
                    for blo, block_arr in _box_blocks(
                            arr, box_lo, block, goff, dlo, dhi):
                        cache.put((token, blo), block_arr)
    if telemetry.enabled():
        n_missing = int(missing.sum())
        if n_missing < len(blocks):
            telemetry.inc("storage/hits", len(blocks) - n_missing)
        if n_missing:
            telemetry.inc("storage/misses", n_missing)
            telemetry.inc("storage/block_reads", n_missing)
            telemetry.inc("storage/read_requests", len(boxes))
            telemetry.inc("storage/bytes_read", bytes_read)
        if cache is not None:
            telemetry.gauge("storage/cache_bytes", cache.nbytes)
    return out


# ---------------------------------------------------------------------------
# the coalescing write path
# ---------------------------------------------------------------------------
def _write_is_aligned(lo, hi, block, goff, dlo, dhi) -> bool:
    """True when ``[lo, hi)`` starts on the block grid and ends on it
    (or at the domain edge, where storage clamps trailing blocks): such
    a write owns whole blocks — no read-modify-write, and parallel
    writers cannot conflict (the aligned-chunk contract)."""
    for d in range(len(lo)):
        if (lo[d] - goff[d]) % block[d] != 0:
            return False
        if hi[d] != dhi[d] and (hi[d] - goff[d]) % block[d] != 0:
            return False
    return True


def blockwise_save(backend: StorageBackend, lo: Sequence[int],
                   arr: np.ndarray, cache: Optional[BlockCache] = None,
                   wait: bool = True, per_block: bool = True):
    """Write ``arr`` at ``lo`` through the coalescing path.

    Block-aligned writes decompose into per-block futures issued
    concurrently — each commits its block directly (no driver-level
    read-modify-write) — and update the cache write-through (a copy of
    the written block replaces any cached version, so read-after-write
    through the cache returns the written bytes even before the commit
    is durable). Unaligned writes fall back to one whole-range driver
    write and *invalidate* every covered block instead, and so does an
    aligned write with ``per_block=False``: for a box of many small
    blocks (a thumbnail level: a thousand blocks of 7 KB) a future and a
    cache copy a block cost more than the blocks, and the driver, which
    is handed whole blocks and reads none back, splits the range on its
    own threads.

    ``wait=True`` blocks until every block is durable (every future
    drained even when one fails; first exception wins). ``wait=False``
    awaits only the copy legs — the caller may reuse the buffer — and
    returns a :class:`GatherFuture` for the write-behind window; the
    ack-after-durable-write barrier (``runtime.drain_pending_writes``)
    drains it exactly like the single-future path it replaces."""
    lo = tuple(int(v) for v in lo)
    hi = tuple(l + s for l, s in zip(lo, arr.shape))
    _check_domain(backend, lo, hi)
    dlo, dhi = backend.domain
    block, goff = backend.block_shape, backend.grid_offset
    aligned = (
        storage_mode() == "concurrent"
        and _write_is_aligned(lo, hi, block, goff, dlo, dhi)
    )
    futures = []
    mode = ("unaligned" if not aligned
            else "aligned" if per_block else "whole")
    with telemetry.span("storage/write", mode=mode):
        if mode == "aligned":
            zero_blocks = 0
            for blo, bhi in _covering_blocks(lo, hi, block, goff,
                                             dlo, dhi):
                sub = arr[tuple(
                    slice(bl - l, bh - l)
                    for l, bl, bh in zip(lo, blo, bhi)
                )]
                futures.append(backend.write_async(blo, bhi, sub))
                if cache is None:
                    zero_blocks += not sub.any()
                    continue
                block_copy = np.array(sub, copy=True)
                if block_copy.any():
                    cache.put((backend.cache_token, blo), block_copy)
                else:
                    # stay consistent with the read path's
                    # zeros-are-never-pinned rule
                    cache.invalidate((backend.cache_token, blo))
                    zero_blocks += 1
            telemetry.inc("storage/aligned_writes")
            # blocks written though all zero (a blank or masked task's):
            # what a volume pays to keep its resume rule
            # (PrecomputedVolume.has_all_blocks)
            telemetry.inc("storage/zero_blocks_written", zero_blocks)
        else:
            futures.append(backend.write_async(lo, hi, arr))
            if cache is not None:
                for blo, _bhi in _covering_blocks(lo, hi, block, goff,
                                                  dlo, dhi):
                    cache.invalidate((backend.cache_token, blo))
            telemetry.inc("storage/aligned_writes" if aligned
                          else "storage/unaligned_writes")
        telemetry.inc("storage/bytes_written", arr.nbytes)
        gathered = GatherFuture(futures)
        if wait:
            gathered.result()
            return None
        # await the COPY legs (the driver reading the source buffer) so
        # callers may freely reuse/mutate the array; only the storage
        # COMMIT stays asynchronous until the drain barrier
        gathered.copy.result()
    return gathered
