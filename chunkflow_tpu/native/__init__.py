"""Native C++ kernels (cc3d / waterz / zmesh equivalents) via ctypes.

The shared library builds on first use with g++ -O3 into ``lib/`` under a
name keyed on the source bytes, the compiler flags and — because of
``-march=native`` — the host CPU, so a library built from other sources
or on another machine (a copied checkout keeps no mtimes) is never
loaded. All entry points are plain C ABI over numpy buffers — no pybind11
dependency (not in this image).
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
from typing import Optional, Tuple

import numpy as np

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
_LIB_DIR = os.path.join(os.path.dirname(__file__), "lib")
_SOURCES = ("cc3d.cpp", "watershed.cpp", "surface_nets.cpp", "remap.cpp")
_HEADERS = ("zslab.h",)
_FLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC", "-march=native",
          "-pthread")

_lib: Optional[ctypes.CDLL] = None


def _host_cpu() -> str:
    """What ``-march=native`` resolves against: the CPU's model and
    feature flags (Linux), else the platform's own description."""
    try:
        with open("/proc/cpuinfo") as f:
            lines = f.read().splitlines()
    except OSError:
        return f"{platform.machine()} {platform.processor()}"
    picked = {}
    for line in lines:
        name = line.split(":", 1)[0].strip()
        if name in ("model name", "flags", "Features"):
            picked.setdefault(name, line)
    return "\n".join(picked.values())


def lib_path() -> str:
    """Where the library for THESE sources, flags and host CPU lives."""
    digest = hashlib.sha256()
    for name in _SOURCES + _HEADERS:
        with open(os.path.join(_SRC_DIR, name), "rb") as f:
            digest.update(f.read())
    digest.update(" ".join(_FLAGS).encode())
    digest.update(_host_cpu().encode())
    return os.path.join(
        _LIB_DIR, f"libchunkflow_native-{digest.hexdigest()[:16]}.so")


def build() -> str:
    """Compile the library from source; returns its path."""
    path = lib_path()
    os.makedirs(_LIB_DIR, exist_ok=True)
    # compile beside the target and rename: two workers starting at
    # once must never load a half-written file
    tmp = f"{path}.{os.getpid()}.tmp"
    cmd = [
        "g++", *_FLAGS,
        *(os.path.join(_SRC_DIR, s) for s in _SOURCES),
        "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    for stale in glob.glob(os.path.join(_LIB_DIR, "libchunkflow_native*.so")):
        if stale != path:
            os.remove(stale)
    return path


def load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    path = lib_path()
    if not os.path.exists(path):
        build()
    lib = ctypes.CDLL(path)

    i64 = ctypes.c_int64
    lib.cc3d_label_u8.restype = ctypes.c_uint32
    lib.cc3d_label_u32.restype = ctypes.c_uint32
    lib.cc3d_label_u64.restype = ctypes.c_uint32
    for fn in (lib.cc3d_label_u8, lib.cc3d_label_u32, lib.cc3d_label_u64):
        fn.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, i64, i64, i64, ctypes.c_int,
        ]
    lib.watershed_agglomerate.restype = ctypes.c_uint32
    lib.watershed_agglomerate.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, i64, i64, i64,
        ctypes.c_float, ctypes.c_float, ctypes.c_float,
    ]
    lib.watershed_agglomerate_scored.restype = ctypes.c_uint32
    lib.watershed_agglomerate_scored.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, i64, i64, i64,
        ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_int,
    ]
    lib.agglomerate_fragments.restype = ctypes.c_uint32
    lib.agglomerate_fragments.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, i64, i64, i64,
        ctypes.c_float, ctypes.c_int,
    ]
    lib.surface_nets_mesh_u32.restype = ctypes.c_int32
    lib.surface_nets_mesh_u32.argtypes = [
        ctypes.c_void_p, i64, i64, i64, ctypes.c_uint32,
        ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(i64), ctypes.POINTER(i64),
    ]
    for fn in (lib.cf_renumber_u32, lib.cf_renumber_u64):
        fn.restype = i64
        fn.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, i64, ctypes.c_uint64,
            ctypes.c_void_p, ctypes.c_void_p, i64,
        ]
    for fn in (lib.cf_remap_u32, lib.cf_remap_u64):
        fn.restype = i64
        fn.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, i64,
            ctypes.c_void_p, ctypes.c_void_p, i64, ctypes.c_int,
        ]
    _lib = lib
    return lib


# ---------------------------------------------------------------------------
# numpy-facing wrappers
# ---------------------------------------------------------------------------
def connected_components(arr: np.ndarray, connectivity: int = 26) -> Tuple[np.ndarray, int]:
    """Label distinct-value 3D regions; returns (labels uint32, count)."""
    lib = load()
    if connectivity not in (6, 18, 26):
        raise ValueError(f"connectivity must be 6/18/26, got {connectivity}")
    if arr.size >= 1 << 32:
        # voxel-index union-find addresses voxels as uint32
        raise ValueError(
            f"volume of {arr.size} voxels exceeds the native kernel's "
            f"2^32 voxel addressing; split the chunk first"
        )
    arr = np.ascontiguousarray(arr)
    if arr.dtype == np.bool_:
        arr = arr.astype(np.uint8)
    out = np.empty(arr.shape, dtype=np.uint32)
    fns = {
        np.dtype(np.uint8): lib.cc3d_label_u8,
        np.dtype(np.uint32): lib.cc3d_label_u32,
        np.dtype(np.uint64): lib.cc3d_label_u64,
    }
    dtype = arr.dtype
    if dtype not in fns:
        if np.dtype(dtype).kind in "iu":
            arr = arr.astype(np.uint64)
            dtype = arr.dtype
        else:
            raise TypeError(f"unsupported dtype for labeling: {dtype}")
    count = fns[dtype](
        arr.ctypes.data, out.ctypes.data, *arr.shape, connectivity
    )
    return out, int(count)


SCORING = {"mean": 0, "max": 1, "min": 2}


def _scoring_code(scoring: str) -> int:
    """mean/max/min, or ``quantileN`` (0 <= N <= 100, e.g. quantile50 =
    the waterz aff50 median config; 256-bin histogram approximation)."""
    if scoring in SCORING:
        return SCORING[scoring]
    if scoring.startswith("quantile"):
        try:
            q = int(scoring[len("quantile"):])
        except ValueError:
            q = -1
        if 0 <= q <= 100:
            return 100 + q
    raise ValueError(
        f"scoring must be one of {sorted(SCORING)} or 'quantileN' "
        f"(0<=N<=100), got {scoring!r}"
    )


def watershed_agglomerate(
    affinity: np.ndarray,
    t_high: float = 0.99,
    t_low: float = 0.3,
    merge_threshold: float = 0.5,
    scoring: str = "mean",
    fragments: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, int]:
    """Affinity map [3, z, y, x] float32 -> (segmentation uint32, count).

    ``scoring`` selects the waterz-style boundary aggregator used for
    merge priority: ``mean`` (default — the reference plugin's
    OneMinus<MeanAffinity<...>> spelling), ``max``, ``min``, or
    ``quantileN`` (the QuantileAffinity<..., N, ...> spellings, e.g.
    ``quantile50`` for the aff50 median config; 256-bin histogram, 1 KB
    per boundary pair). With
    ``fragments`` (a [z, y, x] uint32 pre-segmentation, 0 = background)
    the seed/steepest-ascent phases are skipped and only hierarchical
    agglomeration runs on the given fragments — the reference plugin's
    ``fragments=`` input (waterz agglomerate(affs, fragments=...))."""
    lib = load()
    if affinity.ndim != 4 or affinity.shape[0] != 3:
        raise ValueError(f"need [3, z, y, x] affinities, got {affinity.shape}")
    if affinity[0].size >= 1 << 32:
        # voxel-index union-find addresses voxels as uint32 (same limit
        # as connected_components); wrapping would merge unrelated voxels
        raise ValueError(
            f"volume of {affinity[0].size} voxels exceeds the native "
            f"kernel's 2^32 voxel addressing; split the chunk first"
        )
    scoring_code = _scoring_code(scoring)
    aff = np.ascontiguousarray(affinity, dtype=np.float32)
    out = np.empty(aff.shape[1:], dtype=np.uint32)
    if fragments is not None:
        frags = np.asarray(fragments)
        if tuple(frags.shape) != tuple(aff.shape[1:]):
            raise ValueError(
                f"fragments shape {frags.shape} does not match the "
                f"affinity volume {aff.shape[1:]}"
            )
        if frags.dtype.kind not in "iu":
            raise TypeError(
                f"fragments must be integer labels, got {frags.dtype}"
            )
        if frags.size and (int(frags.max()) > 0xFFFFFFFF
                           or int(frags.min()) < 0):
            # a silent uint32 cast would wrap distinct 64-bit supervoxel
            # ids onto each other and fuse unrelated fragments
            raise ValueError(
                "fragment labels must fit uint32; renumber them first "
                "(native.renumber)"
            )
        frags = np.ascontiguousarray(frags, dtype=np.uint32)
        count = lib.agglomerate_fragments(
            aff.ctypes.data, frags.ctypes.data, out.ctypes.data,
            *aff.shape[1:], float(merge_threshold), scoring_code,
        )
        return out, int(count)
    count = lib.watershed_agglomerate_scored(
        aff.ctypes.data, out.ctypes.data, *aff.shape[1:],
        float(t_high), float(t_low), float(merge_threshold),
        scoring_code,
    )
    return out, int(count)


def mesh_object(seg: np.ndarray, obj_id: int) -> Tuple[np.ndarray, np.ndarray]:
    """Surface-nets mesh of one object: (vertices [N,3] xyz voxel units,
    faces [M,3] uint32)."""
    lib = load()
    seg = np.ascontiguousarray(seg, dtype=np.uint32)
    nv = ctypes.c_int64()
    nf = ctypes.c_int64()
    lib.surface_nets_mesh_u32(
        seg.ctypes.data, *seg.shape, int(obj_id),
        None, None, ctypes.byref(nv), ctypes.byref(nf),
    )
    vertices = np.empty((nv.value, 3), dtype=np.float32)
    faces = np.empty((nf.value, 3), dtype=np.uint32)
    lib.surface_nets_mesh_u32(
        seg.ctypes.data, *seg.shape, int(obj_id),
        vertices.ctypes.data if nv.value else None,
        faces.ctypes.data if nf.value else None,
        ctypes.byref(nv), ctypes.byref(nf),
    )
    return vertices, faces


def renumber(arr: np.ndarray, start_id: int = 1):
    """Compact-relabel a segmentation (0 stays 0): single-pass hash table
    (fastremap.renumber equivalent). Returns (relabeled, {old: new})."""
    lib = load()
    flat = np.ascontiguousarray(arr).reshape(-1)
    fns = {
        np.dtype(np.uint32): lib.cf_renumber_u32,
        np.dtype(np.uint64): lib.cf_renumber_u64,
    }
    if flat.dtype not in fns:
        raise TypeError(f"native renumber supports uint32/uint64, got {flat.dtype}")
    out = np.empty_like(flat)
    # generous first buffer (<=64 MB): EM supervoxel chunks run to millions
    # of labels, and a retry repeats the full O(n) relabel pass
    max_pairs = min(flat.size, 1 << 22) or 1
    while True:
        keys = np.empty(max_pairs, dtype=np.uint64)
        vals = np.empty(max_pairs, dtype=np.uint64)
        n = fns[flat.dtype](
            flat.ctypes.data, out.ctypes.data, flat.size, int(start_id),
            keys.ctypes.data, vals.ctypes.data, max_pairs,
        )
        if n >= 0:
            break
        max_pairs = -n
    if n and int(start_id) + n - 1 > np.iinfo(flat.dtype).max:
        raise OverflowError(
            f"renumbered ids exceed {flat.dtype} (start_id={start_id}, "
            f"{n} labels)"
        )
    mapping = dict(zip(keys[:n].tolist(), vals[:n].tolist()))
    return out.reshape(arr.shape), mapping  # flat -> original zyx


def remap(arr: np.ndarray, mapping, preserve_missing: bool = True) -> np.ndarray:
    """Apply an explicit old->new id mapping (fastremap.remap equivalent)."""
    lib = load()
    flat = np.ascontiguousarray(arr).reshape(-1)
    fns = {
        np.dtype(np.uint32): lib.cf_remap_u32,
        np.dtype(np.uint64): lib.cf_remap_u64,
    }
    if flat.dtype not in fns:
        raise TypeError(f"native remap supports uint32/uint64, got {flat.dtype}")
    keys = np.fromiter(mapping.keys(), dtype=np.uint64, count=len(mapping))
    vals = np.fromiter(mapping.values(), dtype=np.uint64, count=len(mapping))
    if vals.size and int(vals.max()) > np.iinfo(flat.dtype).max:
        # the numpy path raises here too; the C++ cast would silently wrap
        raise OverflowError(
            f"mapping value {int(vals.max())} does not fit {flat.dtype}"
        )
    out = np.empty_like(flat)
    fns[flat.dtype](
        flat.ctypes.data, out.ctypes.data, flat.size,
        keys.ctypes.data, vals.ctypes.data, keys.size,
        1 if preserve_missing else 0,
    )
    return out.reshape(arr.shape)  # flat -> original zyx


def available() -> bool:
    try:
        load()
        return True
    except (subprocess.CalledProcessError, OSError):
        return False
