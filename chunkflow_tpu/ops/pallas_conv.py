"""Pallas TPU kernel: a 'SAME' convolution of an x-folded array whose x
halo is built in VMEM, with the block's epilogue in the same pass.

``models/rsunet.py`` runs levels 0 and 1 *x-folded* (``[B,z,y,X/F,F*C]``:
F neighbouring x positions side by side in the lanes) and XLA convolves
the folded array with a block-banded kernel of three 128-lane block taps
in x, of which the two outer ones hold one position's weights each: per
(kz, ky) it issues three weight tiles a row where a quarter of their
blocks hold weights (PERF.md, PR 38). Here an output block of F positions
reads what it needs and no more:

- the *centre*: its own block, ``[rows, F*C] x [F*C, F*C]``;
- the *halo*: the positions of either x neighbour that the x taps reach
  (one for a 3-tap kernel). It is built in VMEM as an operand of the
  centre's own width with no movement in the lanes: the block shifted by
  one row down the sublanes in its last lanes (block b-1's last position),
  shifted one row up in its first lanes (block b+1's first position), zero
  between, and zero where the block is the first or the last of its row
  (an iota mask: the zero padding of 'SAME' in x). Its weights are the two
  outer block taps laid over each other, whose rows do not meet
  (:func:`halo_kernels`).

So a row costs two MXU passes a (kz, ky) and not three: 18 for 3x3x3
where XLA issues 27, 6 for 1x3x3 where it issues 9. The z and y taps are
offsets on major axes of the block in VMEM and move nothing; beyond an
edge a tap is left out (z) or reads a row of zeros (y), which is its zero.

One grid step is one z plane of one patch: the ``kz`` planes it reads
arrive through block specs (an edge plane's neighbour clamped and its
taps skipped), the plane is walked in chunks of whole y rows, a plane's
taps are one dot a chunk (centre, halo and the ky rows side by side in the
operand's lanes) into a float32 accumulator in VMEM, and the chunk's
epilogue rounds the sum once to the array's dtype, adds the bias, applies
the folded batch norm, the residual and the ReLU in float32 and rounds the
result: what ``XFoldConv``, ``Affine`` and ``RSBlock`` do between them,
never rounded earlier or more often. Operands are bfloat16 (a float32
array's are rounded to it as XLA's TPU default does), products accumulate
in float32. Two neighbours ride along where they would cost a relayout
under XLA (a custom call's operands are plain row-major tiles, which XLA's
own convolutions do not like): the 1x1x1 head that alone reads ``dec0``'s
result, as one more pass in ``conv3``'s epilogue, and the embedding
(1x5x5 on one channel), whose centre, halo and five rows fit one lane
tile and are rotated into it: one pass a row.

Which convolutions take the kernel is ``models/rsunet.py``'s to say
(``kernel_takes``), from shapes, dtype and backend; tests run the kernel
interpreted on the CPU (``interpret=True``) and compile it for a described
chip (``tests/tools/test_aot_cost.py``); ``chip_smoke.py`` compiles and
runs it on the chip.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chunkflow_tpu.core import compile_cache

# Pallas is imported where a kernel is traced and nowhere else: the
# import is a second of a start (three on a host that reads the
# interpreter's files over a network mount), and a process that finds
# its kernels lowered (``compile_cache.lowered_once``) traces none.

OPERAND_DTYPE = jnp.bfloat16  # what the MXU multiplies
# rows of one VMEM tile of the operands: x blocks in these reshape to
# matmul rows for free, two to a packed 32-bit word
OPERAND_SUBLANES = 32 // jnp.dtype(OPERAND_DTYPE).itemsize
LANES = 128                   # of a vreg, a VMEM tile and an MXU pass
CHUNK_ROWS = 1024             # matmul rows a chunk: y rows x x blocks
# MiB of VMEM a core, by the device's kind, where it is not the 128 of
# the v4, v5e and v6e (jax's own table: pallas.tpu.get_tpu_info)
SMALLER_VMEM = {"TPU v2": 16, "TPU v3": 16, "TPU v5": 64, "TPU v5p": 64,
                "TPU7x": 64}


def vmem_limit_bytes() -> int:
    """What one call may take of a core's VMEM: 25/32 of it, 100 MiB of
    the v5e's 128. The core is the one the process runs on; where it runs
    on none (a program lowered for a described chip, the interpreter) it
    is the v5e's, the chip this repository's cells run on."""
    kind = (jax.devices()[0].device_kind
            if jax.default_backend() == "tpu" else "")
    return SMALLER_VMEM.get(kind, 128) * 2 ** 20 * 25 // 32


def vmem_bytes(window: Tuple[int, int], extents: Tuple[int, int],
               width: int, dtype, residual: bool = True) -> int:
    """What a call holds in VMEM at once, to lay against
    :func:`vmem_limit_bytes`: the ``kz`` input planes, the residual's and
    the result's, each ``[ys, blocks, a lane tile]`` and each twice (the
    pipeline fetches a step ahead); the weights twice; the accumulator,
    the operand's rows and the operand a chunk."""
    kz, ky = window
    ys, blocks = extents
    tiles = -(-width // LANES)
    plane = ys * blocks * tiles * LANES * jnp.dtype(dtype).itemsize
    operand = jnp.dtype(OPERAND_DTYPE).itemsize
    chunk = chunk_rows(ys, blocks)
    weights = kz * ky * 2 * tiles * LANES * tiles * LANES * operand
    rows = (chunk + ky) * blocks * 2 * tiles * LANES * operand
    return (2 * (kz + residual + 1) * plane + 2 * weights
            + chunk * blocks * tiles * LANES * 4 + (1 + ky) * rows)


def halo_kernels(folded, channels: int):
    """``(centre, halo)``, each ``[kz,ky,F*Cin,F*Cout]``, from
    ``fold_kernel``'s ``[kz,ky,3,F*Cin,F*Cout]``: the centre is the middle
    block tap; the halo holds block tap -1's rows of the last input
    position (the only ones with weights) and block tap +1's rows of the
    first. Pure data movement: every entry a published weight or 0."""
    kz, ky, taps, lanes_in, _ = folded.shape
    assert taps == 3 and lanes_in >= 2 * channels, folded.shape
    last = (np.arange(lanes_in, dtype=np.int32)
            >= lanes_in - channels)[:, None]
    return folded[:, :, 1], jnp.where(last, folded[:, :, 0], folded[:, :, 2])


# The kernel's body is written in ``lax``'s own operations where it
# repeats (a plane's operands, three times a 27-tap kernel): every process
# that starts traces and lowers each distinct kernel of its program, and a
# ``jnp`` operator costs four times a ``lax`` one to trace.

def _pack(x):
    """``x`` ([rows, x blocks, lanes]) as :data:`OPERAND_DTYPE` in the
    32-bit words its tile packs its rows in: two neighbouring x blocks a
    word, the even one in the low half. Mosaic rotates 32-bit rows only
    ("not implemented: Rotate with non-32-bit data", jax 0.9.0), and a
    bitwise or of two operands wants integers."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.bitcast(lax.convert_element_type(x, OPERAND_DTYPE),
                         jnp.uint32)


def _unpack(packed):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.bitcast(packed, OPERAND_DTYPE)


def _halo_masks(words: int, lanes: int, channels: int, width: int):
    """``(from_right, from_left)``: which bits of a packed word the halo
    takes from the block one row up (``channels`` lanes from lane 0) and
    one row down (the last ``channels`` of the ``width`` lanes that hold
    the F positions); the row's last block has no right neighbour, its
    first no left one. Built once a kernel."""
    word = lax.broadcasted_iota(jnp.int32, (1, words, lanes), 1)
    lane = lax.broadcasted_iota(jnp.int32, (1, words, lanes), 2)
    low, high = jnp.uint32(0xFFFF), jnp.uint32(0xFFFF0000)
    zero = jnp.uint32(0)
    from_right = jnp.where(
        lane < channels, low | jnp.where(word < words - 1, high, zero), zero)
    from_left = jnp.where(
        (lane >= width - channels) & (lane < width),
        high | jnp.where(word > 0, low, zero), zero)
    return from_right, from_left


_HALF = np.uint32(16)  # bits of a packed word's half: one x block


def _halo(packed, masks):
    """The halo operand of a packed block (:func:`_pack`), packed: see
    the module text. A row up or down the x blocks is a half-word shift
    and the other half from the next word."""
    from jax.experimental.pallas import tpu as pltpu

    words = packed.shape[1]
    from_right, from_left = (
        lax.broadcast_in_dim(mask, packed.shape, (0, 1, 2))
        for mask in masks)
    right = lax.bitwise_or(
        lax.shift_right_logical(packed, _HALF),
        lax.shift_left(pltpu.roll(packed, words - 1, axis=1), _HALF))
    left = lax.bitwise_or(
        lax.shift_left(packed, _HALF),
        lax.shift_right_logical(pltpu.roll(packed, 1, axis=1), _HALF))
    return lax.bitwise_or(lax.bitwise_and(right, from_right),
                          lax.bitwise_and(left, from_left))


def _epilogue(acc_ref, terms_ref, residual_ref, head_refs, out_ref, y0,
              chunk: int):
    """One rounding of the sum to the array's dtype, then bias, batch
    norm, residual and ReLU in float32 and the result's rounding
    (``terms_ref``: bias, scale, shift and the floor, 0 or -inf, a row
    each); with a head, the 1x1x1 convolution of that result (one more
    pass, rounded as a convolution of its own, then its bias) is what is
    written."""
    from jax.experimental import pallas as pl

    dtype = out_ref.dtype
    y = acc_ref[...].astype(dtype).astype(jnp.float32)
    y = (y + terms_ref[0:1, :]) * terms_ref[1:2, :] + terms_ref[2:3, :]
    if residual_ref is not None:
        y = y + residual_ref[pl.ds(y0, chunk)].astype(jnp.float32).reshape(
            y.shape)
    y = jnp.maximum(y, terms_ref[3:4, :]).astype(dtype)
    if head_refs is not None:
        kernel_ref, bias_ref = head_refs
        y = jnp.dot(y.astype(OPERAND_DTYPE), kernel_ref[...],
                    preferred_element_type=jnp.float32)
        y = y.astype(dtype).astype(jnp.float32) + bias_ref[...]
        y = y[:, :out_ref.shape[-1]].astype(dtype)
    out_ref[pl.ds(y0, chunk)] = y.reshape(chunk, *out_ref.shape[1:])


def _conv_kernel(*refs, window: Tuple[int, int], channels: int, chunk: int,
                 planes_z: int, residual: bool, head: bool, width: int):
    """One z plane of one patch, a chunk of y rows a loop step (one body
    for every chunk: Mosaic unrolls a dot, and the kernel's compile time
    is its code). A plane's taps reach the MXU as one operand a plane
    and chunk: centre and halo side by side in two lane tiles, the ky
    rows that a chunk's result reads side by side as well, one dot of
    K = ky * 256 whose passes accumulate inside the matmul (on the v5e
    6.36 ms a 27-tap convolution at ``[4,20,256,64,112]``; centre and
    halo as a dot each, summed in the accumulator, 6.80; one dot of
    K = 256 a (kz, ky) 7.28: PERF.md, PR 47). ``width``: the lanes that
    hold the input's F positions. An input of few lanes
    (``ky * 2 * width <= 128``: the embedding), which comes zero-filled
    to a whole lane tile, is *packed*: centre, halo and the ky rows
    rotated into one lane tile, one pass a plane."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kz, ky = window
    planes, refs = refs[:kz], refs[kz:]
    kernel_ref, terms_ref, *refs = refs
    residual_ref, refs = (refs[0], refs[1:]) if residual else (None, refs)
    head_refs, refs = (refs[:2], refs[2:]) if head else (None, refs)
    out_ref, acc_ref, rows_ref = refs
    ys, blocks, lanes_in = planes[0].shape
    z = pl.program_id(1)
    pad = ky // 2
    packed_form = ky * 2 * width <= LANES
    tile = rows_ref.shape[-1] // 2  # centre | halo, where not packed
    masks = _halo_masks(blocks // 2, lanes_in, channels, width)
    zeros = jnp.zeros((pad, blocks // 2, lanes_in), jnp.uint32)

    def operands(plane, y0):
        """The operand's rows from ``plane``'s rows ``[y0 - pad, y0 + chunk
        + pad)``, zeros where a row lies beyond the plane's edge. ``y0``
        is a multiple of ``chunk >= pad``: the rows above lie all inside
        the plane or all beyond it, and so do those below. One halo a
        plane and chunk: a traced op apiece is what a kernel costs every
        process that starts."""
        packed = _pack(plane[pl.ds(y0, chunk)])
        if pad:
            below = lax.add(y0, np.int32(chunk))
            edges = (
                (lax.max(lax.sub(y0, np.int32(pad)), np.int32(0)),
                 lax.gt(y0, np.int32(0))),
                (lax.min(below, np.int32(ys - pad)),
                 lax.lt(below, np.int32(ys))))
            (above, below) = (
                lax.select_n(inside, zeros, _pack(plane[pl.ds(start, pad)]))
                for start, inside in edges)
            packed = lax.concatenate([above, packed, below], 0)
        halo = _halo(packed, masks)
        if packed_form:  # centre and halo side by side in 2 * width lanes
            rows_ref[...] = _unpack(packed | pltpu.roll(halo, width, axis=2))
            return
        rows_ref[:, :, 0:lanes_in] = _unpack(packed)
        rows_ref[:, :, tile:tile + lanes_in] = _unpack(halo)

    def taps(dz: int, y0):
        """What plane ``dz`` gives output rows ``[y0, y0 + chunk)``: its
        rows as operands, then one dot."""
        operands(planes[dz], y0)
        if packed_form:
            operand = _pack(rows_ref[0:chunk])
            for dy in range(1, ky):
                operand |= pltpu.roll(_pack(rows_ref[dy:dy + chunk]),
                                      dy * 2 * width, axis=2)
            operand = _unpack(operand)
        else:
            operand = jnp.concatenate(
                [rows_ref[dy:dy + chunk] for dy in range(ky)], axis=-1)
        return jnp.dot(
            operand.reshape(chunk * blocks, operand.shape[-1]),
            kernel_ref[dz], preferred_element_type=jnp.float32)

    def chunk_of_rows(step, carry):
        y0 = pl.multiple_of(step * chunk, chunk)
        acc_ref[...] = taps(kz // 2, y0)
        for dz in range(kz):
            if dz != kz // 2:
                def add(dz=dz):
                    acc_ref[...] += taps(dz, y0)
                at = z + dz - kz // 2
                pl.when((at >= 0) & (at < planes_z))(add)
        _epilogue(acc_ref, terms_ref, residual_ref, head_refs, out_ref, y0,
                  chunk)
        return carry

    if not packed_form and lanes_in < tile:
        # the lanes no operand writes: zero, not what the scratch held
        blank = jnp.zeros((*rows_ref.shape[:2], tile - lanes_in),
                          OPERAND_DTYPE)
        rows_ref[:, :, lanes_in:tile] = blank
        rows_ref[:, :, tile + lanes_in:] = blank
    lax.fori_loop(0, ys // chunk, chunk_of_rows, 0)


def chunk_rows(ys: int, blocks: int) -> int:
    """The y rows of a chunk: the largest divisor of ``ys`` whose matmul
    rows stay within :data:`CHUNK_ROWS` (on the v5e a 27-tap convolution
    at ``[4,20,256,64,112]`` read 6.56 ms at 1024, 6.36 at 2048 and 4096,
    7.23 at 8192, and Mosaic took 2.0, 6.6, 9.2 and 20.6 s to compile it
    with the first and the last chunk as code of their own: PERF.md,
    PR 47); one row where even that passes it."""
    return max(rows for rows in range(1, ys + 1)
               if ys % rows == 0 and (rows * blocks <= CHUNK_ROWS
                                      or rows == 1))


def folded_conv(x, centre, halo, bias, scale=None, shift=None,
                residual=None, relu: bool = False, head=None, *,
                channels: int, interpret: bool = False):
    """The 'SAME' convolution of ``x`` (``[B,z,y,X/F,F*Cin]``) with the
    folded kernel ``centre`` / ``halo`` (:func:`halo_kernels`), then
    ``+ bias``, ``* scale + shift`` (where given), ``+ residual`` (where
    given) and the ReLU (``relu``): ``[B,z,y,X/F,F*Cout]`` in ``x``'s
    dtype. ``bias``, ``scale``, ``shift``: ``[F*Cout]``, tiled over the
    fold. ``channels``: the lanes of a neighbouring block that the x taps
    reach (Cin for a 3-tap kernel). With a ``head`` (``(kernel [F*Cout,
    F*Chead], bias [F*Chead])``: a folded 1x1x1 convolution of the
    result) what is returned is the head's result, ``[B,z,y,X/F,
    F*Chead]``, and the array between them is never written.

    Lowered once (``core/compile_cache.py`` ``lowered_once``): the calls
    of one program that agree in shapes and epilogue (``enc0`` and
    ``dec0``: a block's ``conv1``, its ``conv2``) are one function of it,
    traced and lowered to Mosaic once, and a process that finds the
    lowering beside jax's compile cache does neither."""
    return compile_cache.lowered_once(
        _folded_conv, dict(relu=relu, channels=channels, interpret=interpret,
                           vmem_limit=vmem_limit_bytes()),
        x, centre, halo, bias, scale, shift, residual, head,
        platform=None if interpret else "tpu")


def _folded_conv(x, centre, halo, bias, scale, shift, residual, head, *,
                 relu: bool, channels: int, interpret: bool,
                 vmem_limit: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, zs, ys, blocks, width = x.shape
    kz, ky, _, lanes_out = centre.shape
    chunk = chunk_rows(ys, blocks)
    assert blocks % OPERAND_SUBLANES == 0, blocks
    assert chunk >= ky // 2, (chunk, ky)
    # the epilogue's rows: where there is no batch norm, times 1 plus 0
    # (exact); where no ReLU, a floor of -inf
    lane = jnp.ones((lanes_out,), jnp.float32)
    terms = jnp.stack([
        jnp.asarray(bias, jnp.float32),
        lane if scale is None else jnp.asarray(scale, jnp.float32),
        0 * lane if shift is None else jnp.asarray(shift, jnp.float32),
        (0 if relu else -jnp.inf) * lane])
    if ky * 2 * width <= LANES:
        # few lanes in (the embedding): zero-filled to a lane tile here,
        # where XLA writes the tile anyway, and packed in the kernel
        x = jnp.pad(x, ((0, 0),) * 4 + ((0, LANES - width),))
        kernel = jnp.pad(
            jnp.concatenate([centre, halo], axis=2).reshape(
                kz, ky * 2 * width, lanes_out),
            ((0, 0), (0, LANES - ky * 2 * width), (0, 0)))
        operand_lanes = LANES
    else:  # rows (ky, centre | halo, lane) of one plane's operand
        tile = -(-width // LANES) * LANES
        fill = ((0, 0), (0, 0), (0, tile - width), (0, 0))
        kernel = jnp.concatenate(
            [jnp.pad(k, fill) for k in (centre, halo)], axis=2).reshape(
                kz, ky * 2 * tile, lanes_out)
        operand_lanes = 2 * tile
    kernel = kernel.astype(OPERAND_DTYPE)

    def plane(lanes, dz=0):
        def index(b, z):
            return b, jnp.clip(z + dz, 0, zs - 1), 0, 0, 0
        return pl.BlockSpec((None, None, ys, blocks, lanes), index)

    def whole(array):
        return pl.BlockSpec(array.shape, lambda b, z: (0,) * array.ndim)

    operands = [x] * kz + [kernel, terms]
    in_specs = [plane(x.shape[-1], dz - kz // 2) for dz in range(kz)] + [
        whole(kernel), whole(terms)]
    if residual is not None:
        operands.append(residual)
        in_specs.append(plane(lanes_out))
    lanes_written = lanes_out
    if head is not None:
        head_kernel, head_bias = head
        lanes_written = head_kernel.shape[-1]
        wide = -lanes_written % LANES  # the head's pass is a lane tile wide
        operands += [
            jnp.pad(head_kernel.astype(OPERAND_DTYPE), ((0, 0), (0, wide))),
            jnp.pad(jnp.asarray(head_bias, jnp.float32), (0, wide))[None]]
        in_specs += [whole(operands[-2]), whole(operands[-1])]
    return pl.pallas_call(
        functools.partial(
            _conv_kernel, window=(kz, ky), channels=channels, chunk=chunk,
            planes_z=zs, residual=residual is not None,
            head=head is not None, width=width),
        grid=(batch, zs),
        in_specs=in_specs,
        out_specs=plane(lanes_written),
        out_shape=jax.ShapeDtypeStruct(
            (batch, zs, ys, blocks, lanes_written), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((chunk * blocks, lanes_out), jnp.float32),
            pltpu.VMEM((chunk + 2 * (ky // 2), blocks, operand_lanes),
                       OPERAND_DTYPE)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(*operands)
