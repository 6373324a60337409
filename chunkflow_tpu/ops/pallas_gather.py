"""Device-resident patch gather: the front half of the patch loop (ISSUE 15).

Before this module the patch loop's *back* half (bump-weighted
accumulation) was fused on device (ops/pallas_blend.py, ISSUE 14) but the
*front* half still had two shapes:

* the per-chunk fused program gathered with ``vmap(dynamic_slice)`` from a
  chunk that ``Inferencer._infer`` had already converted to float32 with
  eager device ops — one full-chunk f32 materialization (4x the bytes of a
  uint8 EM chunk) before the program even started;
* the serving packer gathered, padded and int->f32-converted every patch
  HOST-side and re-uploaded it, so overlapping patches shipped each chunk
  voxel over PCIe ~(patch/stride)^3 times.

This module makes the chunk itself the device-resident operand — uploaded
ONCE, in its RAW dtype (uint8 ships at 1/4 the bytes of float32) — and
gathers patch windows from it by index, the Ragged Paged Attention idiom
(PAPERS.md): the big buffer stays resident, the kernel walks it with a
starts table. Two legs share one selection point:

* the **XLA reference leg** (the measured-winner default): the program's
  front converts the raw chunk to float32 *inside* the program
  (IEEE-exact: int images scale by ``1/iinfo.max``, the same expression
  ``Inferencer._infer`` ran eagerly) and gathers with the proven
  ``vmap(dynamic_slice)`` — bitwise identical to the host front half by
  construction (conversion, edge-padding and slicing are exact value
  copies/roundings that commute);
* the **Pallas kernel leg** (opt-in): :func:`gather_patches` DMAs each
  patch's aligned window out of the RAW resident chunk and applies the
  int->f32 conversion in VMEM per tile — the full-chunk f32
  materialization never exists in HBM. Alignment rules follow the blend
  kernel's round-1 lesson: DMA corners in the two minor dims must be
  *provably* divisible by the dtype's (sublane, 128) tiling, so the
  kernel copies aligned windows and reads the patch at its (dy, dx)
  offset inside the VMEM scratch.

Selection: ``CHUNKFLOW_GATHER`` (re-read per program build, and part of
every blend-family cache key via :func:`gather_key`, so an env flip
REBUILDS instead of reusing a stale program — the CHUNKFLOW_PALLAS/
CHUNKFLOW_MESH convention):

    (unset)/on/device  the device-resident XLA leg (default: bitwise
                       identical to the host front, strictly less H2D)
    off/host           the pre-ISSUE-15 host front half, bit-identically
                       (the kill switch; serving gathers on the host)
    pallas             the compiled Mosaic gather kernel: compiles and
                       matches the XLA leg bitwise on a v5e for float32
                       and uint8 chunks (chip_smoke.py); opt-in, its
                       speed against the XLA leg is not measured
    interpret          the kernel in interpret mode (CPU tests)

Unrecognized values warn ONCE on stderr and resolve to the default
device leg (a typo must not silently fall back to the host round trip,
and must not force-select the compiled Mosaic kernel either).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from chunkflow_tpu.core import envmode

Triple = Tuple[int, int, int]

_DEVICE_VALUES = ("", "1", "on", "true", "device", "xla")
_HOST_VALUES = ("0", "off", "false", "no", "host")
_PALLAS_VALUES = ("pallas", "force")
_MODE_CHOICES = {
    "device": _DEVICE_VALUES,
    "host": _HOST_VALUES,
    "pallas": _PALLAS_VALUES,
    "interpret": ("interpret",),
}
_WARNED_VALUES: set = set()

_LANE = 128


def gather_mode() -> str:
    """'device' | 'host' | 'pallas' | 'interpret' — resolved from
    ``CHUNKFLOW_GATHER`` (re-read per call so tests and long-lived
    workers can flip it; the cache-key tag makes the flip rebuild).
    Unrecognized values warn once and fall to the device leg
    (core/envmode.py holds the shared warn-once contract).

    ``CHUNKFLOW_FUSED_PIPELINE`` (ops/blend.py, ISSUE 17) outranks this
    knob: the fused patch pipeline gathers through the Pallas leg by
    definition, so pipeline 'on'/'interpret' force the matching mode
    here regardless of CHUNKFLOW_GATHER — one knob flips the whole
    pipeline consistently."""
    from chunkflow_tpu.ops import blend

    pipe = blend.fused_pipeline_mode()
    if pipe != "off":
        return "interpret" if pipe == "interpret" else "pallas"
    return envmode.resolve(
        "CHUNKFLOW_GATHER", _MODE_CHOICES, default="device",
        note="using the default device-resident XLA gather — not the "
             "host front half, not the compiled Pallas kernel",
        warned=_WARNED_VALUES,
    )


def gather_tag() -> str:
    """The selected gather front as a cache-key component: ``"dev"``
    (default), ``"host"``, ``"pallas-on"`` or ``"pallas-interpret"``."""
    mode = gather_mode()
    if mode == "device":
        return "dev"
    if mode == "host":
        return "host"
    if mode == "interpret":
        # the kernelcheck sanitizer instruments the interpret trace, so
        # its on/off state is part of the program identity
        from chunkflow_tpu.testing import kernelcheck

        return f"pallas-interpret{kernelcheck.key_suffix()}"
    return "pallas-on"


def gather_key() -> tuple:
    """ProgramCache key suffix for the gather selection: empty for the
    default device leg (historical key strings unchanged),
    ``("gather-<tag>",)`` otherwise — so a ``CHUNKFLOW_GATHER`` flip
    mid-stream builds the right program instead of reusing a stale
    one."""
    tag = gather_tag()
    return () if tag == "dev" else (f"gather-{tag}",)


# ---------------------------------------------------------------------------
# geometry: per-dtype aligned windows
# ---------------------------------------------------------------------------

def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublane(dtype) -> int:
    """Mosaic sublane tiling of the second-minor dim by dtype width:
    f32 (8, 128), 16-bit (16, 128), 8-bit (32, 128). DMA slice corners
    must be provably divisible by this."""
    return {1: 32, 2: 16}.get(np.dtype(dtype).itemsize, 8)


def gather_window(py: int, px: int, dtype) -> Tuple[int, int]:
    """(wy, wx): the dtype-aligned window that covers a (py, px) patch
    placed at any within-window offset (dy, dx)."""
    sub = _sublane(dtype)
    return (_round_up(py + sub - 1, sub), _round_up(px + _LANE - 1, _LANE))


def gather_buffer_padding(pin: Triple, dtype) -> Tuple[int, int]:
    """Extra (Y, X) high-side padding the RAW chunk needs so every
    aligned gather window lies in bounds (worst case: a patch ending
    flush at the chunk edge whose aligned corner rounds down). The pad
    is constant-valued — padded cells are DMA'd but never read into a
    patch."""
    wy, wx = gather_window(pin[1], pin[2], dtype)
    return (wy - pin[1], wx - pin[2])


# ---------------------------------------------------------------------------
# the IEEE-exact conversion shared by every leg
# ---------------------------------------------------------------------------

def convert_chunk(chunk):
    """Raw chunk -> float32, the single definition of the normalization
    every front-half leg applies (host numpy, in-program XLA, in-kernel
    VMEM): int images scale to [0, 1] by ``1/iinfo.max`` (the int->f32
    conversion is exact, the f32 multiply is the same IEEE operation
    everywhere); float32 passes through untouched; other floats round
    with IEEE round-to-nearest."""
    import jax.numpy as jnp

    dt = np.dtype(chunk.dtype)
    if dt.kind in "iu":
        scale = np.float32(1.0 / np.iinfo(dt).max)
        return chunk.astype(jnp.float32) * scale
    if dt == np.float32:
        return chunk
    return chunk.astype(jnp.float32)


def _int_scale(dtype):
    """The normalization scale for an int dtype (None for floats)."""
    dt = np.dtype(dtype)
    if dt.kind in "iu":
        return np.float32(1.0 / np.iinfo(dt).max)
    return None


def raw_eligible(dtype) -> bool:
    """Whether a chunk dtype may ride the device-resident front RAW:
    float32 (no conversion) and int dtypes up to 32 bits (normalized
    in-program). 64-bit ints keep the host-side conversion (x64-disabled
    ``jnp.asarray`` would silently wrap them) and non-f32 floats keep
    the legacy upload-as-f32 path."""
    dt = np.dtype(dtype)
    return dt == np.float32 or (dt.kind in "iu" and dt.itemsize <= 4)


# ---------------------------------------------------------------------------
# the Pallas gather kernel
# ---------------------------------------------------------------------------

def gather_kernel_cost(B: int, ci: int, input_patch_size: Triple,
                       dtype) -> dict:
    """Analytic cost of one :func:`gather_patches` build — the
    builder's own arithmetic, for ``profiling.stamp_cost`` and
    ``tools/kernel_report.py``. VMEM is the GL021 model: the pipelined
    output block double-buffered (dynamic index), plus the raw-dtype
    window scratch; the resident chunk is ANY-space and costs nothing
    on chip. Bytes per step: one aligned raw window in, one f32 patch
    tile out.

    Returns ``{grid_steps, vmem_bytes, bytes_per_step, bytes_accessed,
    flops}``.
    """
    import numpy as np

    pz, py, px = input_patch_size
    itemsize = np.dtype(dtype).itemsize
    wy, wx = gather_window(py, px, dtype)
    vmem = (
        2 * py * px * 4     # out block (1,1,1,py,px) f32: double-buffered
        + wy * wx * itemsize  # raw-dtype window scratch
    )
    grid_steps = B * ci * pz
    step_bytes = wy * wx * itemsize + py * px * 4
    return {
        "grid_steps": grid_steps,
        "vmem_bytes": vmem,
        "bytes_per_step": step_bytes,
        "bytes_accessed": grid_steps * step_bytes,
        # int->f32 scale is one multiply per output voxel; f32 moves only
        "flops": grid_steps * py * px if _int_scale(dtype) else 0,
    }


def gather_patches(chunk, in_starts, input_patch_size: Triple,
                   interpret: bool = False):
    """``out[b] = convert(chunk[:, s:s+pin])`` for every row of the
    starts table — window slicing and int->f32 normalization fused into
    one VMEM pass over the RAW resident chunk.

    chunk:     [ci, Z, Y+pad, X+pad] raw dtype (uint8/uint16/int32/f32),
               high-side padded per :func:`gather_buffer_padding`
    in_starts: [B, 3] int32 zyx corners (within the unpadded extent)
    returns:   [B, ci, pz, py, px] float32

    The DMA only ever copies windows whose (y, x) corners are rounded
    down to the dtype's (sublane, 128) tiling (``pl.multiple_of``
    hints — the blend kernel's round-1 alignment lesson) and the patch
    is read at its (dy, dx) offset inside the VMEM scratch window, where
    the conversion happens in-register."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from chunkflow_tpu.testing import kernelcheck

    check = kernelcheck.active(interpret)
    ci = chunk.shape[0]
    pz, py, px = input_patch_size
    B = in_starts.shape[0]
    dtype = chunk.dtype
    sub = _sublane(dtype)
    wy, wx = gather_window(py, px, dtype)
    scale = _int_scale(dtype)
    narrow_unsigned = (np.dtype(dtype).kind == "u"
                       and np.dtype(dtype).itemsize < 4)

    z0 = in_starts[:, 0]
    y0a = (in_starts[:, 1] // sub) * sub
    x0a = (in_starts[:, 2] // _LANE) * _LANE
    starts_aligned = jnp.stack([z0, y0a, x0a], axis=1)
    dyx = jnp.stack(
        [in_starts[:, 1] - y0a, in_starts[:, 2] - x0a], axis=1
    )

    def kernel(starts_ref, dyx_ref, chunk_ref, out_ref, scratch, sem):
        b = pl.program_id(0)
        c = pl.program_id(1)
        k = pl.program_id(2)
        if check:
            # canary: the full-window DMA below overwrites the poison
            # before any read, so a clean kernel is bit-identical
            kernelcheck.poison_scratch(scratch)
        z = starts_ref[b, 0] + k
        y0 = pl.multiple_of(starts_ref[b, 1], sub)
        x0 = pl.multiple_of(starts_ref[b, 2], _LANE)
        dy = dyx_ref[b, 0]
        dx = dyx_ref[b, 1]
        window = chunk_ref.at[c, z, pl.ds(y0, wy), pl.ds(x0, wx)]
        load = pltpu.make_async_copy(window, scratch, sem)
        load.start()
        load.wait()
        win = scratch[...]
        if narrow_unsigned:
            # Mosaic lowers no unsigned -> float cast ("Unsupported cast:
            # uint8 -> float32", jax 0.9.0); zero-extending to int32
            # first is exact
            win = win.astype(jnp.int32)
        # the same IEEE expression convert_chunk applies chunk-wide:
        # exact int->f32, then one f32 multiply — bitwise equal to
        # convert-then-slice on the XLA leg
        win = win.astype(jnp.float32)
        # Mosaic has no vector load at a dynamic unaligned (dy, dx)
        # ("cannot statically prove that index in dimension 0 is a
        # multiple of 8", v5e), so the window is rotated until the
        # patch sits at its origin and sliced statically
        win = pltpu.roll(pltpu.roll(win, wy - dy, 0), wx - dx, 1)
        tile = win[:py, :px]
        if scale is not None:
            tile = tile * scale
        out_ref[0, 0, 0] = tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, ci, pz),
        in_specs=[
            # the resident chunk is never block-copied wholesale: the
            # kernel DMAs exactly one aligned window per grid step
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, py, px),
            lambda b, c, k, *prefetch: (b, c, k, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((wy, wx), dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
    )

    if check:
        kernelcheck.check_bounds(
            starts_aligned, (pz, wy, wx), chunk.shape[1:],
            "gather_patches",
        )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, ci, pz, py, px), jnp.float32),
        interpret=interpret,
    )(starts_aligned, dyx, chunk)
    if check:
        out = kernelcheck.check_result(out, "gather_patches")
    return out


# ---------------------------------------------------------------------------
# the selection seam every program family builds through
# ---------------------------------------------------------------------------

def make_gather(num_input_channels: int, input_patch_size: Triple):
    """The front-half pair for one (ci, pin) geometry, resolved against
    the live ``CHUNKFLOW_GATHER`` mode at build time (callers fold
    :func:`gather_key` into their cache key so a flip rebuilds):

    ``prepare(chunk) -> chunk_like``
        trace-time front over the RAW chunk: the XLA legs convert to
        float32 once (a no-op for f32 traffic — which is why
        ``CHUNKFLOW_GATHER=off``'s pre-converted chunks run the exact
        historical program); the Pallas legs keep the chunk RAW and only
        apply the constant alignment padding.

    ``gather(chunk_like, s_in) -> [B, ci, *pin] float32``
        one batch of patch windows: ``vmap(dynamic_slice)`` on the XLA
        legs, :func:`gather_patches` on the Pallas legs.

    Both legs produce bitwise-identical float32 patches (conversion and
    slicing commute exactly), which is what keeps every downstream
    parity contract intact no matter the selection. Both trace under
    ``jax.named_scope("gather")`` (metadata only; core/profiling.py
    ``DEVICE_SCOPES``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    ci = num_input_channels
    pin = tuple(input_patch_size)
    mode = gather_mode()

    if mode in ("device", "host"):

        def prepare(chunk):
            with jax.named_scope("gather"):
                return convert_chunk(chunk)

        def gather(chunk_f32, s_in):
            with jax.named_scope("gather"):
                return jax.vmap(
                    lambda s: lax.dynamic_slice(
                        chunk_f32, (0, s[0], s[1], s[2]), (ci,) + pin
                    )
                )(s_in)

        return prepare, gather

    interp = mode == "interpret"

    def prepare(chunk):
        pad_y, pad_x = gather_buffer_padding(pin, chunk.dtype)
        if pad_y or pad_x:
            # constant pad: the aligned DMA windows may cover these
            # cells but no patch ever reads them
            with jax.named_scope("gather"):
                chunk = jnp.pad(
                    chunk, [(0, 0), (0, 0), (0, pad_y), (0, pad_x)]
                )
        return chunk

    def gather(chunk_raw, s_in):
        with jax.named_scope("gather"):
            return gather_patches(chunk_raw, s_in, pin, interpret=interp)

    return prepare, gather
