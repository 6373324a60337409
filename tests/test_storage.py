"""The storage plane (chunkflow_tpu/volume/storage.py, ISSUE 11):
backend interface, block-granular hot-chunk LRU, concurrent block I/O,
the coalescing write path, and the telemetry/scheduler/observability
wiring. Everything here runs against the in-memory backend (no driver,
no disk) except the KV-plane tests, which exercise the real tensorstore
KvStore batched-existence path over a file root."""
import threading

import numpy as np
import pytest

from chunkflow_tpu.core import telemetry
from chunkflow_tpu.volume import storage
from chunkflow_tpu.volume.storage import (
    BlockCache,
    FileKV,
    GatherFuture,
    MemoryBackend,
    TensorStoreKV,
    blockwise_cutout,
    blockwise_save,
    open_kv,
    serial_cutout,
    set_read_concurrency,
    shared_cache,
)


@pytest.fixture(autouse=True)
def _clean_state():
    telemetry.reset()
    storage.reset_shared_cache()
    storage._reset_read_concurrency()
    yield
    telemetry.reset()
    storage.reset_shared_cache()
    storage._reset_read_concurrency()


def _backend(shape=(40, 50, 60), block=(16, 16, 16), seed=0,
             cls=MemoryBackend, **kw):
    rng = np.random.default_rng(seed)
    # 1..255: no all-zero block (zero blocks are deliberately uncached)
    data = rng.integers(1, 255, size=shape, dtype=np.uint8)
    return data, cls(data.copy(), block_shape=block, **kw)


class CountingBackend(MemoryBackend):
    """Records every driver request, and how many were in flight when
    each was issued (issued and not yet awaited by the reader)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.requests = []      # (lo, hi) per read_async
        self.in_flight = []     # requests outstanding at each issue
        self._awaited = 0

    def read_async(self, lo, hi):
        backend = self
        future = super().read_async(lo, hi)

        class Awaited:
            def result(self):
                value = future.result()
                backend._awaited += 1
                return value

        self.requests.append((tuple(lo), tuple(hi)))
        self.in_flight.append(len(self.requests) - self._awaited)
        return Awaited()


def _counting():
    return _backend(cls=CountingBackend)


def _block_by_block(backend, lo, hi):
    """The reference reader of the box path: every covering block read
    on its own and assembled, as the cutout did before it sent boxes."""
    out = np.empty([h - l for l, h in zip(lo, hi)], dtype=backend.dtype)
    dlo, dhi = backend.domain
    for blo, bhi in storage._covering_blocks(
            lo, hi, backend.block_shape, backend.grid_offset, dlo, dhi):
        storage._copy_block(out, lo, hi, backend._read(blo, bhi), blo, bhi)
    return out


# ---------------------------------------------------------------------------
# BlockCache
# ---------------------------------------------------------------------------
def test_cache_lru_eviction_holds_byte_budget():
    cache = BlockCache(3 * 100)
    blocks = {i: np.full(100, i, dtype=np.uint8) for i in range(5)}
    for i in range(4):
        assert cache.put(("t", i), blocks[i])
    assert cache.nbytes <= 300
    assert cache.evictions == 1
    assert cache.get(("t", 0)) is None  # LRU victim
    # touching 1 protects it from the next eviction
    assert cache.get(("t", 1)) is not None
    cache.put(("t", 4), blocks[4])
    assert cache.get(("t", 1)) is not None
    assert cache.get(("t", 2)) is None


def test_cache_refuses_oversized_and_invalidates():
    cache = BlockCache(100)
    assert not cache.put(("t", 0), np.zeros(101, dtype=np.uint8))
    arr = np.ones(50, dtype=np.uint8)
    cache.put(("t", 1), arr)
    # cached blocks are frozen: a writer must go through invalidation
    with pytest.raises(ValueError):
        cache.get(("t", 1))[0] = 9
    assert cache.invalidate(("t", 1))
    assert not cache.invalidate(("t", 1))
    assert cache.nbytes == 0


def test_cache_invalidate_token_scopes_to_one_dataset():
    cache = BlockCache(1 << 20)
    cache.put(("a", (0,)), np.ones(8, dtype=np.uint8))
    cache.put(("a", (8,)), np.ones(8, dtype=np.uint8))
    cache.put(("b", (0,)), np.ones(8, dtype=np.uint8))
    assert cache.invalidate_token("a") == 2
    assert cache.get(("b", (0,))) is not None


def test_shared_cache_env_knobs(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_STORAGE_CACHE_MB", "0")
    assert shared_cache() is None
    monkeypatch.setenv("CHUNKFLOW_STORAGE_CACHE_MB", "1")
    cache = shared_cache()
    assert cache is not None and cache.max_bytes == 1 << 20
    assert shared_cache() is cache  # stable while the budget holds
    monkeypatch.setenv("CHUNKFLOW_STORAGE_CACHE_MB", "2")
    assert shared_cache() is not cache  # resized -> rebuilt


# ---------------------------------------------------------------------------
# concurrent blockwise reads
# ---------------------------------------------------------------------------
def test_blockwise_cutout_bit_identical_on_ragged_windows():
    data, backend = _backend()
    cache = BlockCache(1 << 24)
    windows = [
        ((0, 0, 0), (40, 50, 60)),    # whole volume (ragged tail blocks)
        ((3, 5, 7), (37, 49, 55)),    # interior, nothing aligned
        ((16, 16, 16), (32, 32, 32)),  # exactly one block
        ((39, 49, 59), (40, 50, 60)),  # single trailing voxel
    ]
    for lo, hi in windows:
        out = blockwise_cutout(backend, lo, hi, cache=cache)
        ref = data[tuple(slice(l, h) for l, h in zip(lo, hi))]
        np.testing.assert_array_equal(out, ref)
        np.testing.assert_array_equal(
            serial_cutout(backend, lo, hi), ref)
    backend.close()


def test_overlapping_reads_hit_the_cache():
    data, backend = _backend()
    cache = BlockCache(1 << 24)
    blockwise_cutout(backend, (0, 0, 0), (16, 16, 16), cache=cache)
    assert cache.misses == 1 and cache.hits == 0
    # the halo neighbor overlaps the same block: a hit, plus new misses
    blockwise_cutout(backend, (8, 8, 8), (24, 24, 24), cache=cache)
    assert cache.hits == 1
    assert cache.misses == 1 + 7
    # full repeat: pure hits
    misses = cache.misses
    blockwise_cutout(backend, (8, 8, 8), (24, 24, 24), cache=cache)
    assert cache.misses == misses
    backend.close()


def test_cutout_counters_flow_into_telemetry_and_metrics():
    from chunkflow_tpu.parallel.restapi import render_prometheus

    _data, backend = _backend()
    cache = BlockCache(1 << 24)
    blockwise_cutout(backend, (0, 0, 0), (32, 32, 32), cache=cache)
    blockwise_cutout(backend, (0, 0, 0), (32, 32, 32), cache=cache)
    counters = telemetry.snapshot()["counters"]
    assert counters["storage/misses"] == 8
    assert counters["storage/hits"] == 8
    assert counters["storage/block_reads"] == 8
    assert counters["storage/read_requests"] == 1  # one box, no second read
    assert counters["storage/bytes_read"] == 8 * 16 ** 3
    text = render_prometheus()
    assert "chunkflow_storage_hits_total" in text
    assert "chunkflow_storage_misses_total" in text
    assert "chunkflow_storage_bytes_read_total" in text
    backend.close()


def test_all_zero_blocks_are_never_pinned():
    """A zero block may simply not exist yet (fill_missing rendering):
    caching it would hide a neighbor task's later write forever."""
    data = np.zeros((16, 16, 16), dtype=np.uint8)
    backend = MemoryBackend(data, block_shape=(16, 16, 16))
    cache = BlockCache(1 << 20)
    out = blockwise_cutout(backend, (0, 0, 0), (16, 16, 16), cache=cache)
    assert not out.any() and len(cache) == 0
    # the block gets written out-of-band (another worker); we must see it
    backend._array[:] = 7
    out = blockwise_cutout(backend, (0, 0, 0), (16, 16, 16), cache=cache)
    assert (out == 7).all()
    backend.close()


def test_read_concurrency_waves_stay_correct():
    """What the bound bounds now: the boxes a cutout has in flight.
    A checkerboard of cached blocks leaves every missing block a box of
    its own, the most requests a cutout can need."""
    data, backend = _counting()            # 3 x 4 x 4 blocks
    cache = BlockCache(1 << 24)
    blocks = storage._covering_blocks(
        (0, 0, 0), (40, 50, 60), backend.block_shape, (0, 0, 0),
        *backend.domain)
    for k, (blo, bhi) in enumerate(blocks):
        if sum(np.unravel_index(k, (3, 4, 4))) % 2:
            cache.put((backend.cache_token, blo), np.array(
                data[tuple(slice(l, h) for l, h in zip(blo, bhi))]))
    set_read_concurrency(2)
    out = blockwise_cutout(backend, (0, 0, 0), (40, 50, 60), cache=cache)
    np.testing.assert_array_equal(out, data)
    assert storage.read_concurrency() == 2
    assert len(backend.requests) == 24
    assert max(backend.in_flight) == 2
    backend.close()


# ---------------------------------------------------------------------------
# one driver request a cutout
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("lo, hi, box", [
    ((0, 0, 0), (40, 50, 60), ((0, 0, 0), (40, 50, 60))),    # whole volume
    ((3, 5, 7), (37, 49, 55), ((0, 0, 0), (40, 50, 60))),    # nothing aligned
    ((17, 18, 19), (30, 31, 32), ((16, 16, 16), (32, 32, 32))),  # one block
    ((20, 20, 20), (36, 36, 36), ((16, 16, 16), (40, 48, 48))),  # 2x2x2
    # at the domain's edge the box clamps as the trailing blocks do
    ((33, 49, 50), (40, 50, 60), ((32, 48, 48), (40, 50, 60))),
])
@pytest.mark.parametrize("with_cache", [False, True])
def test_cold_cutout_is_one_driver_request(lo, hi, box, with_cache):
    data, backend = _counting()
    cache = BlockCache(1 << 24) if with_cache else None
    out = blockwise_cutout(backend, lo, hi, cache=cache)
    assert backend.requests == [box]
    np.testing.assert_array_equal(out, _block_by_block(backend, lo, hi))
    np.testing.assert_array_equal(
        out, data[tuple(slice(l, h) for l, h in zip(lo, hi))])
    if with_cache:
        # every block of the box is in the cache, as memory of its own
        # and no more (a view would keep the whole box alive)
        blocks = storage._covering_blocks(
            lo, hi, backend.block_shape, (0, 0, 0), *backend.domain)
        assert len(cache) == len(blocks)
        for blo, bhi in blocks:
            cached = cache.get((backend.cache_token, blo))
            owner = cached
            while getattr(owner, "base", None) is not None:
                owner = owner.base
            assert len(memoryview(owner).cast("B")) == cached.nbytes
            assert not cached.flags.writeable
            np.testing.assert_array_equal(
                cached, data[tuple(slice(l, h) for l, h in zip(blo, bhi))])
        # and the same cutout again asks the driver for nothing; the
        # first result is the caller's to write on (where the box is the
        # request it is the driver's own array, and the cache holds copies)
        assert out.flags.writeable and out.flags.c_contiguous
        out[...] = 0
        again = blockwise_cutout(backend, lo, hi, cache=cache)
        assert backend.requests == [box]
        np.testing.assert_array_equal(
            again, data[tuple(slice(l, h) for l, h in zip(lo, hi))])
    backend.close()


def _cached(pattern):
    """Grid indices (3 x 4 x 4 blocks) of the blocks to cache first."""
    cells = list(np.ndindex(3, 4, 4))
    return {
        "one in the middle": [(1, 1, 2)],
        "two apart": [(0, 1, 1), (2, 2, 3)],
        "the neighbor's halo (a slab)": [c for c in cells if c[2] == 0],
        "a corner": [(0, 0, 0)],
        "a row through the middle": [c for c in cells
                                     if c[0] == 1 and c[1] == 2],
        "all but one": [c for c in cells if c != (2, 3, 3)],
        "all": cells,
    }[pattern]


@pytest.mark.parametrize("pattern, n_boxes", [
    ("one in the middle", 6), ("two apart", 8),
    ("the neighbor's halo (a slab)", 1), ("a corner", 3),
    ("a row through the middle", 4), ("all but one", 1), ("all", 0),
])
def test_cached_blocks_leave_the_few_boxes_around_them(pattern, n_boxes):
    """The boxes tile exactly the missing blocks: none covers a cached
    block, none is read twice, and the bytes are the block-by-block
    path's."""
    data, backend = _counting()
    cache = BlockCache(1 << 24)
    cached = _cached(pattern)
    for idx in cached:
        blo = tuple(16 * i for i in idx)
        bhi = tuple(min(b + 16, d) for b, d in zip(blo, data.shape))
        # what the cache holds differs from the driver's bytes, so a box
        # laid over a cached block would show
        cache.put((backend.cache_token, blo), np.full(
            [h - l for l, h in zip(blo, bhi)], 255, dtype=np.uint8))
    out = blockwise_cutout(backend, (1, 2, 3), (40, 50, 60), cache=cache)
    want = data.copy()
    covered = np.zeros(data.shape, dtype=int)
    for idx in cached:
        sel = tuple(slice(16 * i, 16 * i + 16) for i in idx)
        want[sel] = 255
        covered[sel] += 1
    for lo, hi in backend.requests:
        covered[tuple(slice(l, h) for l, h in zip(lo, hi))] += 1
    assert (covered == 1).all()
    assert len(backend.requests) == n_boxes
    np.testing.assert_array_equal(out, want[1:, 2:, 3:])
    counters = telemetry.snapshot()["counters"]
    assert counters.get("storage/read_requests", 0) == n_boxes
    assert counters.get("storage/block_reads", 0) == 48 - len(cached)
    assert counters.get("storage/hits", 0) == len(cached)
    backend.close()


@pytest.mark.parametrize("shape, holes", [
    ((1,), []), ((5,), [(2,)]), ((3, 4), [(1, 1), (2, 3)]),
    ((3, 4, 4), [(1, 1, 2)]), ((19, 13, 3, 1), [(9, 6, 1, 0)]),
    ((4, 4), [(i, j) for i in range(4) for j in range(4) if (i + j) % 2]),
    ((2, 3), [(i, j) for i in range(2) for j in range(3)]),
])
def test_tile_boxes_tile_the_true_cells_exactly(shape, holes):
    mask = np.ones(shape, dtype=bool)
    for hole in holes:
        mask[hole] = False
    covered = np.zeros(shape, dtype=int)
    boxes = storage._tile_boxes(mask)
    for start, stop in boxes:
        covered[tuple(slice(a, b) for a, b in zip(start, stop))] += 1
    np.testing.assert_array_equal(covered, mask.astype(int))
    assert len(boxes) <= max(1, 2 * len(shape) * len(holes))
    if not holes:
        assert boxes == [((0,) * len(shape), shape)]


@pytest.mark.parametrize("dtype, dlo, dhi, goff, block, box_lo, box_hi", [
    # ragged at the domain's end
    ("uint8", (0, 0, 0), (40, 50, 60), (0, 0, 0), (16, 16, 16),
     (0, 0, 0), (40, 50, 60)),
    # a box inside, whole blocks only
    ("uint8", (0, 0, 0), (40, 50, 60), (0, 0, 0), (16, 16, 16),
     (16, 16, 32), (32, 48, 48)),
    # xyzc with the channels in the block, float32
    ("float32", (0, 0, 0, 0), (20, 12, 9, 3), (0, 0, 0, 0), (8, 4, 4, 3),
     (0, 0, 0, 0), (20, 12, 9, 3)),
    # the grid anchored before the domain: the first block is clamped too
    ("uint16", (0, 0), (30, 21), (-5, -3), (8, 8), (0, 0), (30, 21)),
    # one block
    ("uint8", (0,), (7,), (0,), (16,), (0,), (7,)),
    # a dtype whose buffer format is no single character
    ("complex64", (0, 0), (9, 8), (0, 0), (4, 4), (0, 0), (9, 8)),
])
def test_box_blocks_are_the_blocks_of_the_box(dtype, dlo, dhi, goff, block,
                                              box_lo, box_hi):
    rng = np.random.default_rng(5)
    shape = [h - l for l, h in zip(box_lo, box_hi)]
    arr = rng.integers(1, 200, size=shape).astype(dtype)
    blocks = storage._covering_blocks(box_lo, box_hi, block, goff, dlo, dhi)
    zero = blocks[len(blocks) // 2]
    sel = lambda b: tuple(                              # noqa: E731
        slice(l - o, h - o) for l, h, o in zip(b[0], b[1], box_lo))
    if len(blocks) > 1:
        arr[sel(zero)] = 0
    got = dict(storage._box_blocks(arr, box_lo, block, goff, dlo, dhi))
    want = {b[0]: arr[sel(b)] for b in blocks
            if len(blocks) == 1 or b is not zero}
    assert sorted(got) == sorted(want)
    for blo, block_arr in got.items():
        assert block_arr.dtype == arr.dtype
        np.testing.assert_array_equal(block_arr, want[blo])
        assert not np.shares_memory(block_arr, arr)


def test_cached_bytes_win_over_the_drivers():
    """Read-after-write through the cache: a block written with
    ``wait=False`` and not yet durable is served from the cache, in the
    middle of a cutout whose other blocks come from the driver."""
    data, backend = _counting()
    cache = BlockCache(1 << 24)

    class Done:
        def result(self):
            return None

    class Pending:
        """The source is copied, the commit waits for the drain: the
        write-behind window held open."""
        copy = Done()

        def __init__(self, lo, hi, arr):
            self.args = (lo, hi, np.array(arr))

        def result(self):
            backend._write(*self.args)

    backend.write_async = Pending
    w = np.full((16, 16, 16), 200, dtype=np.uint8)
    pending = blockwise_save(backend, (16, 16, 16), w, cache=cache,
                             wait=False)
    out = blockwise_cutout(backend, (0, 0, 0), (40, 50, 60), cache=cache)
    # the driver still holds the old bytes; the cutout must not
    np.testing.assert_array_equal(backend._array[16:32, 16:32, 16:32],
                                  data[16:32, 16:32, 16:32])
    want = data.copy()
    want[16:32, 16:32, 16:32] = 200
    np.testing.assert_array_equal(out, want)
    assert all(
        not all(l <= 16 and h >= 32 for l, h in zip(lo, hi))
        for lo, hi in backend.requests)
    pending.result()
    np.testing.assert_array_equal(
        serial_cutout(backend, (0, 0, 0), (40, 50, 60)), want)
    backend.close()


def test_zero_block_inside_a_box_is_not_pinned():
    data, backend = _counting()
    backend._array[16:32, 32:48, 0:16] = 0       # one absent block
    cache = BlockCache(1 << 24)
    out = blockwise_cutout(backend, (0, 0, 0), (40, 50, 60), cache=cache)
    assert len(backend.requests) == 1
    assert not out[16:32, 32:48, 0:16].any()
    assert len(cache) == 47
    assert cache.get((backend.cache_token, (16, 32, 0))) is None
    # the neighbor writes it; the next cutout asks the driver for that
    # block alone and sees the write
    backend._array[16:32, 32:48, 0:16] = 9
    out = blockwise_cutout(backend, (0, 0, 0), (40, 50, 60), cache=cache)
    assert backend.requests[1:] == [((16, 32, 0), (32, 48, 16))]
    assert (out[16:32, 32:48, 0:16] == 9).all()
    backend.close()


def test_read_span_carries_requests_beside_blocks(tmp_path):
    import json

    path = telemetry.configure(str(tmp_path))
    _data, backend = _counting()
    blockwise_cutout(backend, (0, 0, 0), (40, 50, 60))
    telemetry.flush()
    events = [json.loads(line) for line in open(path).read().splitlines()
              if line]
    assert [(e["mode"], e["blocks"], e["requests"]) for e in events
            if e["kind"] == "span" and e["name"] == "storage/read"] == [
        ("blockwise", 48, 1)]
    backend.close()


def test_precomputed_cutout_is_one_request_and_stays_strict(
        tmp_path, monkeypatch):
    """Through the real driver: a cold cutout of a ``file://`` volume is
    one ``read_async`` whose bytes are the serial path's; absent blocks
    read as zeros and are not pinned; strict mode still raises."""
    pytest.importorskip("tensorstore")
    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.core.bbox import BoundingBox
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    vol = PrecomputedVolume.create(
        str(tmp_path / "v"), volume_size=(24, 40, 48), dtype="uint8",
        voxel_size=(1, 1, 1), block_size=(8, 16, 16))
    rng = np.random.default_rng(3)
    top = rng.integers(1, 255, size=(16, 40, 48), dtype=np.uint8)
    vol.save(Chunk(top))                     # z [16, 24) is never written
    backend = vol._backend(0)
    requests = []
    read_async = backend.read_async
    monkeypatch.setattr(
        backend, "read_async",
        lambda lo, hi: requests.append((lo, hi)) or read_async(lo, hi))
    storage.reset_shared_cache()
    box = BoundingBox((1, 3, 5), (24, 40, 47))
    out = np.asarray(vol.cutout(box).array)
    assert requests == [((0, 0, 0, 0), (48, 40, 24, 1))]   # xyzc, clamped
    want = np.zeros((24, 40, 48), dtype=np.uint8)
    want[:16] = top
    np.testing.assert_array_equal(out, want[1:, 3:, 5:47])
    assert len(shared_cache()) == 2 * 3 * 3  # the written blocks only
    monkeypatch.setenv("CHUNKFLOW_STORAGE", "serial")
    np.testing.assert_array_equal(np.asarray(vol.cutout(box).array), out)
    monkeypatch.delenv("CHUNKFLOW_STORAGE")
    with pytest.raises(FileNotFoundError):
        vol.cutout(box, fill_missing=False)


def test_out_of_domain_requests_raise():
    _data, backend = _backend()
    with pytest.raises(ValueError):
        blockwise_cutout(backend, (0, 0, 0), (41, 50, 60))
    with pytest.raises(ValueError):
        serial_cutout(backend, (-1, 0, 0), (8, 8, 8))
    backend.close()


def test_cache_is_thread_safe_across_tasks():
    """The LRU is shared across tasks in a worker: hammer one cache from
    worker threads doing overlapping cutouts + invalidations (locksmith
    proxies every lock in the suite, so ordering violations raise)."""
    data, backend = _backend(shape=(32, 32, 32), block=(8, 8, 8))
    cache = BlockCache(1 << 16)  # small: force concurrent evictions
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(30):
                lo = tuple(int(v) for v in rng.integers(0, 16, size=3))
                hi = tuple(l + 16 for l in lo)
                out = blockwise_cutout(backend, lo, hi, cache=cache)
                ref = data[tuple(slice(l, h) for l, h in zip(lo, hi))]
                if not np.array_equal(out, ref):
                    errors.append((lo, hi))
                if rng.random() < 0.2:
                    cache.invalidate((backend.cache_token, lo))
        except Exception as exc:  # noqa: BLE001 — surface in main thread
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert not errors, errors[:3]
    backend.close()


# ---------------------------------------------------------------------------
# the coalescing write path
# ---------------------------------------------------------------------------
def test_aligned_save_is_write_through():
    data, backend = _backend()
    cache = BlockCache(1 << 24)
    rng = np.random.default_rng(1)
    w = rng.integers(1, 255, size=(16, 32, 16), dtype=np.uint8)
    blockwise_save(backend, (16, 16, 16), w, cache=cache)
    # durable in the backend...
    np.testing.assert_array_equal(
        serial_cutout(backend, (16, 16, 16), (32, 48, 32)), w)
    # ...and read-after-write through the cache returns the written
    # bytes WITHOUT touching storage (poke the backing array to prove
    # the blocks are cache-served)
    with backend._lock:
        backend._array[16:32, 16:48, 16:32] = 0
    out = blockwise_cutout(backend, (16, 16, 16), (32, 48, 32),
                           cache=cache)
    np.testing.assert_array_equal(out, w)
    assert telemetry.snapshot()["counters"]["storage/aligned_writes"] == 1
    backend.close()


def test_trailing_clamped_blocks_count_as_aligned():
    """A write ending at the domain edge owns its (clamped) trailing
    blocks — the same clamping the storage layout itself applies."""
    data, backend = _backend()          # 40x50x60, blocks 16^3
    w = np.full((8, 2, 12), 9, dtype=np.uint8)
    blockwise_save(backend, (32, 48, 48), w)  # hi == domain stop
    counters = telemetry.snapshot()["counters"]
    assert counters["storage/aligned_writes"] == 1
    assert "storage/unaligned_writes" not in counters
    np.testing.assert_array_equal(
        serial_cutout(backend, (32, 48, 48), (40, 50, 60)), w)
    backend.close()


def test_unaligned_save_invalidates_covered_blocks():
    data, backend = _backend()
    cache = BlockCache(1 << 24)
    blockwise_cutout(backend, (16, 16, 16), (32, 32, 32), cache=cache)
    assert len(cache) == 1
    u = np.full((8, 8, 8), 77, dtype=np.uint8)
    blockwise_save(backend, (20, 20, 20), u, cache=cache)
    assert len(cache) == 0  # covered block dropped
    out = blockwise_cutout(backend, (16, 16, 16), (32, 32, 32),
                           cache=cache)
    assert (out[4:12, 4:12, 4:12] == 77).all()
    counters = telemetry.snapshot()["counters"]
    assert counters["storage/unaligned_writes"] == 1
    assert counters["storage/bytes_written"] == u.nbytes
    backend.close()


def test_save_wait_false_returns_drainable_future():
    data, backend = _backend(latency_s=0.001)
    w = np.full((16, 16, 16), 5, dtype=np.uint8)
    future = blockwise_save(backend, (0, 0, 0), w, wait=False)
    assert future is not None
    # the copy leg is already awaited: mutating the source must not
    # corrupt the committed bytes
    w[:] = 0
    future.result()
    np.testing.assert_array_equal(
        serial_cutout(backend, (0, 0, 0), (16, 16, 16)),
        np.full((16, 16, 16), 5, dtype=np.uint8))
    backend.close()


def test_gather_future_drains_all_and_raises_first():
    class Boom:
        def __init__(self, exc=None):
            self.drained = False
            self.exc = exc

        def result(self):
            self.drained = True
            if self.exc is not None:
                raise self.exc

    ok1, bad, ok2 = Boom(), Boom(RuntimeError("x")), Boom()
    gathered = GatherFuture([ok1, bad, ok2])
    with pytest.raises(RuntimeError, match="x"):
        gathered.result()
    # every member drained even though one failed (the
    # drain_pending_writes contract)
    assert ok1.drained and bad.drained and ok2.drained


# ---------------------------------------------------------------------------
# the KV plane
# ---------------------------------------------------------------------------
def test_file_kv_roundtrip_and_exists(tmp_path):
    kv = open_kv({"driver": "file", "path": str(tmp_path)})
    assert isinstance(kv, FileKV)
    assert kv.read_bytes("info") is None
    kv.write_bytes("sub/dir/blob", b"abc")
    assert kv.read_bytes("sub/dir/blob") == b"abc"
    assert kv.exists_many(["sub/dir/blob", "nope"]) == {
        "sub/dir/blob": True, "nope": False}


def test_tensorstore_kv_batched_existence(tmp_path):
    """The remote-path existence check must be a batched key listing —
    one round trip for a whole task grid's blocks, never a full-value
    download per block (ISSUE 11 satellite)."""
    pytest.importorskip("tensorstore")
    kv = TensorStoreKV({"driver": "file", "path": str(tmp_path)})
    kv.write_bytes("scale/0-16_0-16_0-16", b"\x00" * 64)
    kv.write_bytes("scale/16-32_0-16_0-16", b"\x00" * 64)
    names = ["scale/0-16_0-16_0-16", "scale/16-32_0-16_0-16",
             "scale/32-48_0-16_0-16"]
    assert kv.exists_many(names) == {
        names[0]: True, names[1]: True, names[2]: False}
    assert kv.exists_many([]) == {}
    # the handle is opened once and cached on the backend
    assert kv.kv is kv.kv


# ---------------------------------------------------------------------------
# scheduler integration: the storage depth knob
# ---------------------------------------------------------------------------
def test_depth_controller_widens_storage_on_load_stall():
    from chunkflow_tpu.flow.scheduler import DepthController

    ctl = DepthController(interval=1, min_share=0.4)
    assert ctl.depths["storage"] == storage.read_concurrency()
    before = ctl.depths["storage"]
    # a load-dominated window widens prefetch AND storage, and pushes
    # the widened parallelism to the live storage plane
    ctl.tick({"scheduler/load": 10.0})
    assert ctl.depths["storage"] == before + 1
    assert storage.read_concurrency() == before + 1
    assert ctl.depths["prefetch"] > ctl.initial["prefetch"]


def test_depth_controller_storage_knob_excluded_from_memory_model():
    from chunkflow_tpu.flow.scheduler import DepthController

    ctl = DepthController()
    assert ctl.resident_slots() == sum(
        v for k, v in ctl.depths.items() if k != "storage")


# ---------------------------------------------------------------------------
# observability: the log-summary STORAGE block + lint gate
# ---------------------------------------------------------------------------
def test_log_summary_storage_block(capsys):
    from chunkflow_tpu.flow.log_summary import (
        print_storage_block,
        summarize_telemetry,
    )

    events = [{
        "kind": "snapshot", "t": 1.0, "worker": "w1",
        "counters": {"storage/hits": 30, "storage/misses": 10,
                     "storage/bytes_read": 4096,
                     "storage/aligned_writes": 2},
        "gauges": {"storage/cache_bytes": 2 << 20},
        "hists": {},
    }]
    agg = summarize_telemetry(events)
    assert print_storage_block(agg)
    out = capsys.readouterr().out
    assert "storage/hits" in out
    assert "block cache hit rate 75%" in out
    # quiet for runs that never touched the storage plane
    assert not print_storage_block(summarize_telemetry([]))


def test_fleet_summary_reports_storage_hit_rate():
    from chunkflow_tpu.flow.log_summary import summarize_fleet

    events = [{
        "kind": "snapshot", "t": 1.0, "worker": "w1",
        "counters": {"storage/hits": 8, "storage/misses": 2},
        "gauges": {}, "hists": {},
    }]
    fleet = summarize_fleet(events)
    assert fleet["w1"]["storage_hit_rate"] == pytest.approx(0.8)


def test_storage_plane_is_graftlint_clean():
    """ISSUE 11 satellite: GL001-GL014 clean over the new/reworked
    storage-plane modules, asserted in-suite (the whole-repo gate in
    tests/tools/test_graftlint_gate.py covers them too; this pins the
    specific modules so a future baseline regeneration cannot quietly
    grandfather a concurrency finding here)."""
    from pathlib import Path

    from tools.graftlint.config import load_config
    from tools.graftlint.engine import lint_paths

    repo_root = Path(__file__).resolve().parents[1]
    config = load_config(repo_root / "pyproject.toml")
    findings, _ = lint_paths(
        [
            "chunkflow_tpu/volume/storage.py",
            "chunkflow_tpu/volume/precomputed.py",
            "chunkflow_tpu/plugins/load_tensorstore.py",
            "chunkflow_tpu/plugins/load_n5.py",
        ],
        config, repo_root=repo_root,
    )
    assert not findings, [
        f"{f.path}:{f.line}: {f.code} {f.message}" for f in findings
    ]
