"""Host-side watershed/agglomeration throughput bench (VERDICT r4 #3).

Generates a synthetic Voronoi affinity volume at the inference bench
geometry (64x512x512, overridable via BENCH_SHAPE=z,y,x) and times
`native.watershed_agglomerate` end-to-end plus per-phase (set
CHUNKFLOW_WATERSHED_TIMING=1 when invoking).  The reference runs this
stage through the waterz wheel on dedicated CPU fleets
(reference plugins/agglomerate.py:35-43); here it shares the worker, so
its throughput must keep up with the on-chip inference target
(>= 6.64 Mvox/s).

Run CPU-only:  JAX_PLATFORMS=cpu \
    CHUNKFLOW_WATERSHED_TIMING=1 python tools/bench_watershed.py
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def voronoi_affinity(shape, n_objects=600, noise=0.1, inside=0.9,
                     boundary=0.1, seed=0):
    """Analytic Voronoi ground truth -> 3-channel affinity. Labels come
    from a cKDTree nearest-seed query over the full voxel grid (~800 MB
    of int64 temporaries at 64x512x512 — watch BENCH_SHAPE upscaling)."""
    from scipy.spatial import cKDTree

    from chunkflow_tpu.chunk import AffinityMap

    rng = np.random.default_rng(seed)
    seeds = np.stack([rng.uniform(0, s, n_objects) for s in shape], axis=1)
    tree = cKDTree(seeds)
    zz, yy, xx = np.meshgrid(*(np.arange(s) for s in shape), indexing="ij")
    pts = np.stack([zz.ravel(), yy.ravel(), xx.ravel()], 1)
    _, nearest = tree.query(pts, workers=-1)
    gt = (nearest + 1).reshape(shape).astype(np.uint32)
    aff = np.asarray(
        AffinityMap.from_segmentation(gt, inside=inside, boundary=boundary)
        .array
    )
    aff = aff + rng.normal(0, noise, aff.shape).astype(np.float32)
    return np.clip(aff, 0, 1).astype(np.float32), gt


def main():
    shape = tuple(
        int(v) for v in os.environ.get("BENCH_SHAPE", "64,512,512").split(",")
    )
    from chunkflow_tpu import native

    t0 = time.perf_counter()
    aff, gt = voronoi_affinity(shape)
    gen_s = time.perf_counter() - t0

    native.load()  # build outside the timed region
    # warmup on a small block so page faults/alloc paths are primed
    native.watershed_agglomerate(aff[:, :8, :64, :64], 0.9, 0.3, 0.5)

    repeats = int(os.environ.get("BENCH_REPEATS", "3"))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        seg, count = native.watershed_agglomerate(aff, 0.9, 0.3, 0.5)
        best = min(best, time.perf_counter() - t0)

    nvox = int(np.prod(shape))
    from chunkflow_tpu.chunk.segmentation import Segmentation

    m = Segmentation(seg).evaluate(gt)
    out = {
        "metric": "watershed_agglomerate_mvox_per_s",
        "shape": list(shape),
        "value": round(nvox / best / 1e6, 3),
        "seconds": round(best, 3),
        "segments": int(count),
        "fixture_gen_s": round(gen_s, 2),
        "adjusted_rand_index": round(float(m["adjusted_rand_index"]), 4),
        "voi": round(float(m["voi_split"] + m["voi_merge"]), 4),
        "threads": os.environ.get("CHUNKFLOW_NATIVE_THREADS", "auto"),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
