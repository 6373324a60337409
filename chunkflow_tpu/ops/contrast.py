"""Contrast normalization from histogram sidecars (parity: reference
chunk/image/base.py:93-133, clamping-value search :30-62).

Each z-section of a uint8 image is sent through a 256-entry lookup table
built from the section's *precomputed* histogram: the "levels" file
``<levels path>/<z>``, a JSON object whose ``levels`` is 256 counts (what
igneous's luminance-levels task leaves under ``<image>/levels/<mip>/``),
and two clip fractions. With the histogram ``h`` (``h[0]`` set to 0: pure
black carries no information), ``cdf`` its running sum and ``total`` its
last entry::

    lo = the last v with cdf[v] / total <= lower_clip_fraction   (else 0)
    hi = the last v with cdf[v] / total <= 1 - upper_clip_fraction
    table[v] = clip(round((v - lo) * (maxval - minval) / max(hi - lo, 1))
                    + minval, minval, maxval)          v = 0 .. 255

(``round``: half to even; an empty histogram gives ``lo = hi = 0``.)
Nothing of the chunk is measured: the chunk is only looked up. A table is
built once a ``(path, z, fractions, range)`` and cached
(``normalize/level_reads`` counts the files read,
``normalize/table_cache_hits`` the sections that found theirs).

The chunk is normalized where it is (``chunk.is_on_device``), as
``ops/mask.py`` masks it: a **host** chunk by ``np.take`` a section, uint8
in and out and no float copy; a **device** chunk by one jitted program a
shape, built through :class:`~chunkflow_tpu.core.compile_cache.
ProgramCache` under the named scope ``normalize_contrast``
(core/profiling.py ``DEVICE_SCOPES``), to which only the sections' tables
go up. Both are a lookup in the same tables, so they agree bit for bit.
A section without its sidecar is an error: there is no fallback.
"""
from __future__ import annotations

import json
from typing import Dict, Tuple

import numpy as np

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core import telemetry
from chunkflow_tpu.core.compile_cache import ProgramCache
from chunkflow_tpu.volume.precomputed import _kvstore_spec
from chunkflow_tpu.volume.storage import open_kv

# one program a (chunk shape)
_PROGRAMS = ProgramCache(maxsize=16, label="normalize_contrast")

# (levels path, z, lower, upper, minval, maxval) -> uint8[256]
_TABLES: Dict[tuple, np.ndarray] = {}
# tables are 256 bytes: a volume of a hundred thousand sections fits
_MAX_TABLES = 1 << 17


def clamping_values(levels, lower_clip_fraction: float,
                    upper_clip_fraction: float) -> Tuple[int, int]:
    """``(lo, hi)`` of one section's histogram: the grey values between
    which the clip fractions leave the section's voxels."""
    hist = np.array(levels, dtype=np.int64)
    if hist.shape != (256,):
        raise ValueError(f"a levels histogram has 256 counts, got "
                         f"{hist.shape}")
    hist[0] = 0
    cdf = np.cumsum(hist)
    total = int(cdf[-1])
    if total == 0:
        return 0, 0
    share = cdf / total
    # the last value whose share is within the fraction; 0 where none is
    lo = int(np.searchsorted(share, lower_clip_fraction, side="right")) - 1
    hi = int(np.searchsorted(share, 1.0 - upper_clip_fraction,
                             side="right")) - 1
    return max(lo, 0), max(hi, 0)


def lookup_table(levels, lower_clip_fraction: float = 0.01,
                 upper_clip_fraction: float = 0.01, minval: int = 1,
                 maxval: int = 255) -> np.ndarray:
    """The section's uint8[256] table (module docstring)."""
    if not 0 <= minval <= maxval <= 255:
        raise ValueError(f"need 0 <= minval <= maxval <= 255, got "
                         f"{minval}, {maxval}")
    lo, hi = clamping_values(levels, lower_clip_fraction,
                             upper_clip_fraction)
    # integers throughout: a quotient rounded to the nearest, ties to even
    span = max(hi - lo, 1)
    quotient, rest = np.divmod(
        (np.arange(256, dtype=np.int64) - lo) * (maxval - minval), span)
    stretched = quotient + ((2 * rest > span)
                            | ((2 * rest == span) & (quotient % 2 == 1)))
    return np.clip(stretched + minval, minval, maxval).astype(np.uint8)


def _read_levels(kv, levels_path: str, z: int):
    data = kv.read_bytes(str(z))
    if not data:
        raise FileNotFoundError(
            f"normalize-contrast: no levels file {levels_path}/{z}: the "
            f"histogram of section {z} has to be computed first (there "
            f"is no fallback to the chunk's own percentiles)")
    return json.loads(data)["levels"]


def section_tables(levels_path: str, z_start: int, z_stop: int,
                   lower_clip_fraction: float = 0.01,
                   upper_clip_fraction: float = 0.01, minval: int = 1,
                   maxval: int = 255) -> np.ndarray:
    """uint8[z_stop - z_start, 256]: the tables of the sections, from the
    cache or from their sidecars."""
    settings = (float(lower_clip_fraction), float(upper_clip_fraction),
                int(minval), int(maxval))
    tables = np.empty((z_stop - z_start, 256), np.uint8)
    reads = 0
    kv = None      # the sidecars' store, opened at the first miss
    with telemetry.span("normalize/levels", sections=z_stop - z_start) as sp:
        for row, z in enumerate(range(z_start, z_stop)):
            key = (levels_path, z) + settings
            table = _TABLES.get(key)
            if table is None:
                if kv is None:
                    kv = open_kv(_kvstore_spec(levels_path))
                table = lookup_table(_read_levels(kv, levels_path, z),
                                     *settings)
                reads += 1
                if len(_TABLES) >= _MAX_TABLES:
                    _TABLES.clear()
                _TABLES[key] = table
            tables[row] = table
        hits = z_stop - z_start - reads
        sp.annotate(reads=reads, cache_hits=hits)
    telemetry.inc("normalize/level_reads", reads)
    telemetry.inc("normalize/table_cache_hits", hits)
    return tables


def clear_tables() -> None:
    """Forget every table (tests; a levels directory computed anew)."""
    _TABLES.clear()


def _apply_host(arr: np.ndarray, tables: np.ndarray) -> np.ndarray:
    out = np.empty_like(arr)
    for row, table in enumerate(tables):
        # every channel of the section at once: [..., z, y, x]
        np.take(table, arr[..., row, :, :], out=out[..., row, :, :])
    return out


def _build_program():
    import jax
    import jax.numpy as jnp

    def program(arr, tables):
        with jax.named_scope("normalize_contrast"):
            # section z of the chunk reads row z of the tables
            rows = jnp.arange(arr.shape[-3], dtype=jnp.int32)[:, None, None]
            return tables[rows, arr.astype(jnp.int32)]

    # no donation: the caller keeps the chunk it gave (`-i a -o b`)
    return jax.jit(program)  # graftlint: disable=GL005


def _apply_device(arr, tables: np.ndarray):
    import jax

    key = ("normalize_contrast", tuple(arr.shape))
    return _PROGRAMS.get(key, _build_program)(arr, jax.device_put(tables))


def normalize_sections(chunk: Chunk, tables: np.ndarray) -> Chunk:
    """``chunk`` (uint8, ``[z, y, x]`` or ``[c, z, y, x]``) with section
    ``i`` looked up in ``tables[i]``, on the host or on the device:
    wherever the chunk is."""
    if np.dtype(chunk.dtype) != np.uint8:
        raise TypeError(
            f"normalize-contrast --levels-path looks a uint8 image up in "
            f"256-entry tables; the chunk is {np.dtype(chunk.dtype)} "
            f"(without --levels-path the operator is the percentile "
            f"stretch, which takes any dtype)")
    if tables.shape != (chunk.shape[-3], 256):
        raise ValueError(f"{tables.shape[0]} tables for a chunk of "
                         f"{chunk.shape[-3]} sections")
    on_device = chunk.is_on_device
    with telemetry.span("normalize/apply",
                        voxels=int(np.prod(chunk.shape[-3:])),
                        device=int(on_device)):
        if on_device:
            return chunk._with_array(_apply_device(chunk.array, tables))
        return chunk._with_array(_apply_host(np.asarray(chunk.array),
                                             tables))


def normalize_contrast_by_levels(chunk: Chunk, levels_path: str,
                                 lower_clip_fraction: float = 0.01,
                                 upper_clip_fraction: float = 0.01,
                                 minval: int = 1, maxval: int = 255) -> Chunk:
    """The chunk with every z-section through the table of its global
    section ``chunk.voxel_offset.z + i`` (module docstring)."""
    z0 = int(chunk.voxel_offset[0])
    tables = section_tables(
        levels_path.rstrip("/"), z0, z0 + chunk.shape[-3],
        lower_clip_fraction, upper_clip_fraction, minval, maxval)
    return normalize_sections(chunk, tables)
