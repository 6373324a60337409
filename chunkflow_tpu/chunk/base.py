"""Chunk: an array (numpy or jax) + voxel offset/size + layer type.

The core data model (parity target: reference chunk/base.py — ndarray with
global-coordinate metadata, ufunc interop, cutout/save/blend geometry ops).
TPU-first differences from the reference:

- the payload may live on device as a ``jax.Array``; ``device()`` / ``host()``
  move it explicitly, and compute operators work in jnp either way;
- spatial geometry always refers to the trailing 3 (z, y, x) dims, so 3D
  (zyx) and 4D (czyx) chunks flow through the same code paths — fixing the
  reference's acknowledged 3D/4D wart (load_precomputed.py:78-82);
- ``blend`` (overlap-add) is jit-friendly: it is also exposed as a pure
  function in :mod:`chunkflow_tpu.ops.blend` used inside the fused inference
  loop; the method here is the host-side convenience.
"""
from __future__ import annotations

import os
from enum import Enum
from typing import Optional, Union

import numpy as np

from chunkflow_tpu.core.bbox import BoundingBox
from chunkflow_tpu.core.cartesian import Cartesian, to_cartesian


class LayerType(str, Enum):
    IMAGE = "image"
    SEGMENTATION = "segmentation"
    AFFINITY_MAP = "affinity_map"
    PROBABILITY_MAP = "probability_map"
    UNKNOWN = "unknown"


def _is_jax(array) -> bool:
    return type(array).__module__.startswith("jax")


def as_native_dtype(arr: np.ndarray) -> np.ndarray:
    """Widen non-native dtypes (ml_dtypes bfloat16 and friends, numpy
    kind 'V') to float32 for host file formats that cannot store them
    (HDF5/TIFF/PNG/NRRD writers share this rule)."""
    if arr.dtype.kind not in "biufc":
        return arr.astype(np.float32)
    return arr


class Chunk(np.lib.mixins.NDArrayOperatorsMixin):
    """An ndarray located in a global voxel coordinate system."""

    #: the all-zero answer where it was taken beforehand, None where
    #: nobody asked: ``Inferencer.stage`` sets it on the chunk it hands
    #: the pipeline (asked of the host payload, before the upload). A
    #: chunk derived from this one starts at None again.
    blank: Optional[bool] = None

    def __init__(
        self,
        array,
        voxel_offset=None,
        voxel_size=None,
        layer_type: Union[str, LayerType, None] = None,
    ):
        if isinstance(array, Chunk):
            voxel_offset = voxel_offset or array.voxel_offset
            voxel_size = voxel_size or array.voxel_size
            layer_type = layer_type or array.layer_type
            array = array.array
        if not _is_jax(array):
            array = np.asarray(array)
        if array.ndim not in (3, 4):
            raise ValueError(
                f"chunks are 3D (zyx) or 4D (czyx); got shape {array.shape}"
            )
        self.array = array
        self.voxel_offset = to_cartesian(voxel_offset) or Cartesian.zeros()
        self.voxel_size = to_cartesian(voxel_size) or Cartesian(1, 1, 1)
        if layer_type is None:
            layer_type = self._infer_layer_type(array)
        self.layer_type = LayerType(layer_type)

    @staticmethod
    def _infer_layer_type(array) -> LayerType:
        dtype = np.dtype(array.dtype)
        if array.ndim == 4 and array.shape[0] == 3 and dtype.kind == "f":
            return LayerType.AFFINITY_MAP
        if dtype == np.uint8 and array.ndim == 3:
            return LayerType.IMAGE
        if dtype.kind in "iu" and dtype.itemsize >= 4:
            return LayerType.SEGMENTATION
        if dtype.kind == "f":
            return LayerType.PROBABILITY_MAP
        return LayerType.UNKNOWN

    # ---- factories -----------------------------------------------------
    @classmethod
    def create(
        cls,
        size=(64, 64, 64),
        dtype=np.uint8,
        voxel_offset=(0, 0, 0),
        voxel_size=(1, 1, 1),
        pattern: str = "sin",
        nchannels: Optional[int] = None,
        seed: int = 0,
    ) -> "Chunk":
        """Synthetic test chunk: smooth ``sin`` product, ``random``, ``zero``."""
        size = tuple(to_cartesian(size))
        dtype = np.dtype(dtype)
        if pattern == "zero":
            arr = np.zeros(size, dtype=np.float32)
        elif pattern == "random":
            rng = np.random.default_rng(seed)
            arr = rng.random(size)
        elif pattern == "sin":
            z, y, x = np.meshgrid(
                # float64 linspace keeps the sin fixture bit-stable
                *[np.linspace(0, 4 * np.pi, s)  # graftlint: disable=GL004
                  for s in size], indexing="ij"
            )
            arr = (np.sin(z) * np.sin(y) * np.sin(x) + 1.0) / 2.0
        else:
            raise ValueError(f"unknown pattern {pattern!r}")
        if dtype.kind in "iu":
            arr = (arr * np.iinfo(dtype).max).astype(dtype)
        else:
            arr = arr.astype(dtype)
        if nchannels is not None:
            arr = np.broadcast_to(arr[None, ...], (nchannels,) + size).copy()
        return cls(arr, voxel_offset=voxel_offset, voxel_size=voxel_size)

    @classmethod
    def from_bbox(
        cls, bbox: BoundingBox, dtype=np.float32, nchannels=None, voxel_size=None
    ) -> "Chunk":
        shape = tuple(bbox.shape)
        if nchannels is not None:
            shape = (nchannels,) + shape
        return cls(
            np.zeros(shape, dtype=dtype),
            voxel_offset=bbox.start,
            voxel_size=voxel_size,
        )

    @classmethod
    def from_array(cls, array, bbox: BoundingBox, voxel_size=None) -> "Chunk":
        """Wrap an array whose spatial extent is ``bbox`` (reference
        chunk/base.py:98-106)."""
        if tuple(array.shape[-3:]) != tuple(bbox.shape):
            raise ValueError(
                f"array spatial shape {tuple(array.shape[-3:])} does not "
                f"match bbox shape {tuple(bbox.shape)}"
            )
        return cls(array, voxel_offset=bbox.start, voxel_size=voxel_size)

    # ---- array protocol -------------------------------------------------
    @property
    def shape(self):
        return self.array.shape

    @property
    def dtype(self):
        return self.array.dtype

    @property
    def ndim(self) -> int:
        return self.array.ndim

    @property
    def nchannels(self) -> int:
        return self.array.shape[0] if self.ndim == 4 else 1

    def __len__(self):
        return len(self.array)

    def __array__(self, dtype=None, copy=None):
        arr = np.asarray(self.array)
        return arr.astype(dtype) if dtype is not None else arr

    _HANDLED = (np.ndarray, int, float, complex, np.number, bool, list, tuple)

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        """numpy interop: ``chunk * mask``, ``chunk / 255`` keep metadata."""
        out = kwargs.get("out", ())
        for item in inputs + out:
            if not isinstance(item, self._HANDLED + (Chunk,)) and not _is_jax(item):
                return NotImplemented
        unwrapped = tuple(i.array if isinstance(i, Chunk) else i for i in inputs)
        if out:
            kwargs["out"] = tuple(
                o.array if isinstance(o, Chunk) else o for o in out
            )
        result = getattr(ufunc, method)(*unwrapped, **kwargs)
        if method == "at":
            return None
        if isinstance(result, tuple):
            return tuple(self._rewrap(r) for r in result)
        if out:
            return self._rewrap(kwargs["out"][0])
        return self._rewrap(result)

    def _rewrap(self, result):
        if (
            hasattr(result, "ndim")
            and result.ndim in (3, 4)
            and result.shape[-3:] == self.shape[-3:]
        ):
            return Chunk(
                result,
                voxel_offset=self.voxel_offset,
                voxel_size=self.voxel_size,
                layer_type=self.layer_type,
            )
        return result

    def __getitem__(self, key):
        return self.array[key]

    def __setitem__(self, key, value):
        if _is_jax(self.array):
            self.array = self.array.at[key].set(value)
        else:
            self.array[key] = value

    def __repr__(self) -> str:
        return (
            f"Chunk(shape={self.shape}, dtype={self.dtype}, "
            f"offset={tuple(self.voxel_offset)}, layer={self.layer_type.value})"
        )

    # ---- device movement -------------------------------------------------
    def device(self, sharding=None) -> "Chunk":
        """Move payload to the default accelerator (or given sharding).
        The payload ships in its RAW dtype — uint8 rides the wire at 1/4
        the bytes of float32; conversion happens on device inside the
        inference program (ops/pallas_gather.py). This is the staging
        seam: host-resident payloads count ``transfer/h2d_bytes``."""
        import jax

        if not self.is_on_device:
            from chunkflow_tpu.core import profiling

            profiling.note_h2d(np.asarray(self.array).nbytes)
        arr = jax.device_put(self.array, sharding)
        return self._with_array(arr)

    def host(self) -> "Chunk":
        return self._with_array(np.asarray(self.array))

    @property
    def is_on_device(self) -> bool:
        return _is_jax(self.array)

    def _with_array(self, array) -> "Chunk":
        return type(self)(
            array,
            voxel_offset=self.voxel_offset,
            voxel_size=self.voxel_size,
            layer_type=self.layer_type,
        )

    def astype(self, dtype) -> "Chunk":
        return self._with_array(self.array.astype(dtype))

    def clone(self) -> "Chunk":
        arr = self.array if _is_jax(self.array) else self.array.copy()
        return self._with_array(arr)

    # ---- layer predicates ------------------------------------------------
    @property
    def is_image(self) -> bool:
        return self.layer_type is LayerType.IMAGE

    @property
    def is_segmentation(self) -> bool:
        return self.layer_type is LayerType.SEGMENTATION

    @property
    def is_affinity_map(self) -> bool:
        return self.layer_type is LayerType.AFFINITY_MAP

    @property
    def is_probability_map(self) -> bool:
        return self.layer_type is LayerType.PROBABILITY_MAP

    # ---- geometry --------------------------------------------------------
    @property
    def voxel_stop(self) -> Cartesian:
        return self.voxel_offset + Cartesian.from_collection(self.shape[-3:])

    @property
    def bbox(self) -> BoundingBox:
        return BoundingBox(self.voxel_offset, self.voxel_stop)

    # reference-API surface (chunk/base.py:517-760): drop-in spellings
    @property
    def bounding_box(self) -> BoundingBox:
        return self.bbox

    @property
    def start(self) -> Cartesian:
        return self.voxel_offset

    @property
    def stop(self) -> Cartesian:
        return self.voxel_stop

    @property
    def size(self):
        return self.array.size

    @property
    def ndoffset(self) -> tuple:
        """Offset with the channel dim prepended for 4D chunks."""
        if self.ndim == 4:
            return (0,) + tuple(self.voxel_offset)
        return tuple(self.voxel_offset)

    @property
    def slices(self) -> tuple:
        """Global-coordinate slices of this chunk in the big volume."""
        return tuple(
            slice(o, o + s) for o, s in zip(self.ndoffset, self.shape)
        )

    @property
    def properties(self) -> dict:
        return {
            "voxel_offset": self.voxel_offset,
            "voxel_size": self.voxel_size,
            "layer_type": self.layer_type,
        }

    @properties.setter
    def properties(self, value: dict) -> None:
        self.set_properties(value)

    def set_properties(self, properties: dict) -> None:
        # None values (e.g. JSON nulls) leave the attribute unchanged —
        # nulling voxel_offset would defer a crash to bbox/slices
        if properties.get("voxel_offset") is not None:
            self.voxel_offset = to_cartesian(properties["voxel_offset"])
        if properties.get("voxel_size") is not None:
            self.voxel_size = to_cartesian(properties["voxel_size"])
        if properties.get("layer_type") is not None:
            self.layer_type = LayerType(properties["layer_type"])

    def fill(self, x) -> None:
        if _is_jax(self.array):
            import jax.numpy as jnp

            self.array = jnp.full_like(self.array, x)
        else:
            self.array.fill(x)

    def where(self, mask) -> tuple:
        """np.where in GLOBAL coordinates (reference chunk/base.py:739)."""
        mask = np.asarray(mask)
        if mask.shape != tuple(self.shape):
            raise ValueError(
                f"mask shape {mask.shape} != chunk shape {tuple(self.shape)}"
            )
        return tuple(
            i + o for i, o in zip(np.where(mask), self.ndoffset)
        )

    def ascontiguousarray(self) -> "Chunk":
        if not _is_jax(self.array):
            self.array = np.ascontiguousarray(self.array)
        return self

    def _rel_slices(self, bbox: BoundingBox) -> tuple:
        rel = bbox.translate(-self.voxel_offset)
        spatial = rel.slices
        if self.ndim == 4:
            return (slice(None),) + spatial
        return spatial

    def cutout(self, bbox: BoundingBox) -> "Chunk":
        """Extract a sub-chunk in global coordinates."""
        if not self.bbox.contains(bbox):
            raise ValueError(f"{bbox} not inside chunk bbox {self.bbox}")
        arr = self.array[self._rel_slices(bbox)]
        return type(self)(
            arr,
            voxel_offset=bbox.start,
            voxel_size=self.voxel_size,
            layer_type=self.layer_type,
        )

    def save(self, patch: "Chunk") -> None:
        """Overwrite the region covered by ``patch`` (global coords)."""
        inter = self.bbox.intersection(patch.bbox)
        if not inter.is_valid():
            return
        src = patch.cutout(inter)
        sl = self._rel_slices(inter)
        value = src.array.astype(self.dtype)
        if _is_jax(self.array):
            self.array = self.array.at[sl].set(value)
        else:
            self.array[sl] = value

    def blend(self, patch: "Chunk") -> None:
        """Overlap-add ``patch`` into this chunk (global coords)."""
        inter = self.bbox.intersection(patch.bbox)
        if not inter.is_valid():
            return
        src = patch.cutout(inter)
        sl = self._rel_slices(inter)
        value = src.array.astype(self.dtype)
        if _is_jax(self.array):
            self.array = self.array.at[sl].add(value)
        else:
            self.array[sl] += value

    def add_overlap(self, other: "Chunk") -> None:
        """Sum the overlapping region of ``other`` into this chunk
        (reference chunk/base.py:750)."""
        self.blend(other)

    def shrink(self, size) -> "Chunk":
        """Trim voxels from the faces; ``size`` is 3 symmetric or 6
        (-z,-y,-x,+z,+y,+x) amounts (reference chunk/base.py:630-646)."""
        size = tuple(int(s) for s in size)
        if len(size) == 3:
            size = size + size
        if len(size) != 6:
            raise ValueError(f"need 3 or 6 elements, got {len(size)}")
        if any(s < 0 for s in size):
            raise ValueError(f"shrink amounts must be non-negative: {size}")
        z, y, x = self.shape[-3:]
        if size[0] + size[3] >= z or size[1] + size[4] >= y or \
                size[2] + size[5] >= x:
            raise ValueError(
                f"shrink {size} consumes the whole extent {(z, y, x)}"
            )
        arr = self.array[
            ...,
            size[0]:z - size[3],
            size[1]:y - size[4],
            size[2]:x - size[5],
        ]
        return type(self)(
            arr,
            voxel_offset=self.voxel_offset + Cartesian.from_collection(size[:3]),
            voxel_size=self.voxel_size,
            layer_type=self.layer_type,
        )

    def crop_margin(self, margin) -> "Chunk":
        """Shrink symmetrically by ``margin`` voxels per face."""
        margin = to_cartesian(margin)
        if margin == Cartesian.zeros():
            return self
        return self.cutout(self.bbox.adjust(-margin))

    def pad_to(self, shape, mode: str = "constant") -> "Chunk":
        """Pad (at the stop side) so spatial dims reach ``shape``."""
        target = tuple(to_cartesian(shape))
        current = self.shape[-3:]
        pad = [(0, t - c) for t, c in zip(target, current)]
        if all(p == (0, 0) for p in pad):
            return self
        if any(p[1] < 0 for p in pad):
            raise ValueError(f"cannot pad {current} down to {target}")
        if self.ndim == 4:
            pad = [(0, 0)] + pad
        arr = np.pad(np.asarray(self.array), pad, mode=mode)
        return self._with_array(arr)

    def transpose(self, only_spatial: bool = True) -> "Chunk":
        """Reverse spatial axis order (zyx <-> xyz)."""
        if self.ndim == 4:
            arr = self.array.transpose(0, 3, 2, 1) if only_spatial else self.array.transpose(3, 2, 1, 0)
        else:
            arr = self.array.transpose(2, 1, 0)
        return type(self)(
            arr,
            voxel_offset=Cartesian(*reversed(self.voxel_offset)),
            voxel_size=Cartesian(*reversed(self.voxel_size)),
            layer_type=self.layer_type,
        )

    def squeeze_channel(self) -> "Chunk":
        if self.ndim == 3:
            return self
        if self.shape[0] != 1:
            raise ValueError(f"cannot squeeze {self.shape[0]} channels")
        return self._with_array(self.array[0])

    # ---- analytics / transforms -----------------------------------------
    def all_zero(self) -> bool:
        if _is_jax(self.array):
            # reduce on device: only the scalar crosses D2H (np.asarray
            # here would pull the whole chunk over the link)
            import jax.numpy as jnp

            return not bool(jnp.any(self.array))
        return not bool(np.any(self.array))

    def min(self):
        return self.array.min()

    def max(self):
        return self.array.max()

    def threshold(self, threshold: float) -> "Chunk":
        from chunkflow_tpu.ops import threshold as _threshold

        return _threshold.threshold(self, threshold)

    def connected_component(
        self, threshold: float = 0.5, connectivity: int = 26,
        device: bool = False,
    ) -> "Chunk":
        from chunkflow_tpu.ops import connected_components as _cc

        return _cc.connected_components(
            self, threshold=threshold, connectivity=connectivity,
            device=device,
        )

    def channel_voting(self) -> "Chunk":
        from chunkflow_tpu.ops import voting

        return voting.channel_voting(self)

    def mask_using_last_channel(self, threshold: float = 0.3) -> "Chunk":
        from chunkflow_tpu.ops import voting

        return voting.mask_using_last_channel(self, threshold=threshold)

    def maskout(self, mask: "Chunk") -> "Chunk":
        from chunkflow_tpu.ops import mask as _mask

        return _mask.maskout(self, mask)

    def validate(self) -> bool:
        """Detect black-box corruption by template matching
        (reference chunk/validate.py:6-74)."""
        from chunkflow_tpu.chunk.validate import validate_by_template_matching

        return validate_by_template_matching(np.asarray(self.array))

    def gaussian_filter_2d(self, sigma: float = 1.0) -> "Chunk":
        from chunkflow_tpu.ops import filters

        return filters.gaussian_filter_2d(self, sigma=sigma)

    # ---- I/O -------------------------------------------------------------
    def to_h5(
        self,
        path: str,
        compression: str = "gzip",
        with_unique: bool = False,
        chunk_size=None,
        with_offset: bool = True,
    ) -> str:
        import h5py

        if not path.endswith(".h5"):
            path = os.path.join(path, f"{self.bbox.string}.h5")
        with h5py.File(path, "w") as f:
            # HDF5 has no bfloat16: h5py would store opaque |V2 bytes
            arr = as_native_dtype(np.asarray(self.array))
            chunks = None
            if chunk_size is not None:
                chunks = tuple(chunk_size)
                if arr.ndim == 4 and len(chunks) == 3:
                    chunks = (arr.shape[0],) + chunks
                chunks = tuple(min(c, s) for c, s in zip(chunks, arr.shape))
            f.create_dataset(
                "main", data=arr, compression=compression, chunks=chunks
            )
            if with_offset:
                f.create_dataset("voxel_offset", data=self.voxel_offset.vec)
            f.create_dataset("voxel_size", data=self.voxel_size.vec)
            f.attrs["layer_type"] = self.layer_type.value
            if with_unique and self.is_segmentation:
                f.create_dataset(
                    "unique_nonzeros",
                    data=np.unique(np.asarray(self.array)[np.asarray(self.array) > 0]),
                )
        return path

    @classmethod
    def from_h5(
        cls,
        path: str,
        dataset_path: str = "main",
        voxel_offset=None,
        voxel_size=None,
        bbox: Optional[BoundingBox] = None,
        dtype=None,
        channels=None,
    ) -> "Chunk":
        import h5py

        with h5py.File(path, "r") as f:
            if voxel_offset is None and "voxel_offset" in f:
                voxel_offset = Cartesian(*f["voxel_offset"][()].tolist())
            if voxel_size is None and "voxel_size" in f:
                voxel_size = Cartesian(*f["voxel_size"][()].tolist())
            layer_type = f.attrs.get("layer_type", None)
            dset = f[dataset_path]
            if bbox is not None:
                offset = to_cartesian(voxel_offset) or Cartesian.zeros()
                rel = bbox.translate(-offset)
                sl = rel.slices
                if dset.ndim == 4:
                    sl = (slice(None),) + sl
                arr = dset[sl]
                voxel_offset = bbox.start
            else:
                arr = dset[()]
        if channels is not None and arr.ndim == 4:
            if isinstance(channels, str):
                idx = [int(c) for c in channels.split(",") if c.strip()]
            else:
                idx = [int(c) for c in channels]
            arr = arr[idx]
        if dtype is not None:
            arr = arr.astype(dtype)
        return cls(
            arr,
            voxel_offset=voxel_offset,
            voxel_size=voxel_size,
            layer_type=layer_type,
        )

    def to_tif(self, path: str, compression: str = "zlib") -> str:
        from chunkflow_tpu.volume import io_tif

        return io_tif.write_tif(self, path, compression=compression)

    def with_voxel_size(self, voxel_size) -> "Chunk":
        """Same data, different physical voxel size."""
        out = self._with_array(self.array)
        out.voxel_size = Cartesian.from_collection(voxel_size)
        return out

    @classmethod
    def from_tif(cls, path: str, voxel_offset=None, voxel_size=None, dtype=None):
        from chunkflow_tpu.volume import io_tif

        return io_tif.read_tif(
            path, voxel_offset=voxel_offset, voxel_size=voxel_size, dtype=dtype
        )

    def to_npy(self, path: str) -> str:
        np.save(path, np.asarray(self.array))
        return path

    @classmethod
    def from_npy(cls, path: str, voxel_offset=None, voxel_size=None) -> "Chunk":
        return cls(np.load(path), voxel_offset=voxel_offset, voxel_size=voxel_size)
