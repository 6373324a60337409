"""Fused Pallas TPU kernel: bump weighting + aligned-window placement +
overlap-add accumulation in one VMEM-resident pass (ISSUE 14).

Before this kernel the blend hot loop was three separate device legs:

1. the bump-weight multiply (``preds * bump * valid``) materialized a
   weighted prediction stack AND a weight-patch stack in HBM
   (``ops/blend.py`` ``forward_batch``);
2. an XLA-side ``vmap(dynamic_update_slice)`` pre-scattered each patch
   into its (8,128)-aligned zero-padded window — materializing BOTH
   padded stacks (up to several x wider than the patch for small
   patches) in HBM;
3. the DMA kernel re-read the padded stacks and did the HBM
   read-modify-write.

The fused kernel takes the RAW engine predictions, the validity vector
and the bump constant, and does weighting, placement and the HBM
read-modify-write per grid step entirely in VMEM: the bump map rides
VMEM once for the whole grid (constant-index block — the pipeline skips
the re-copy when the block index does not change), the per-patch
prediction tile streams in at its raw (unpadded) size, and the only HBM
traffic left is the aligned-window read-modify-write the accumulation
fundamentally needs. Nothing is pre-scattered; no weighted, weight-patch
or padded stack exists anymore.

Alignment rules are unchanged from the round-1 hardware failure: Mosaic
requires DMA slice corners in the two minor dims *provably* divisible by
the (8,128) tiling, so the kernel DMAs aligned windows
(``pl.multiple_of`` hints) and adds the contribution at its (dy, dx)
offset *inside* the VMEM scratch window — by rotating the zero-extended
contribution into place, because Mosaic has no vector load or store at a
dynamic unaligned offset either. The TPU grid is sequential, so
overlapping patches accumulate without races, in ascending patch order —
the same duplicate-update order ``lax.scatter_add`` applies, which is
what makes the float32 fused path BITWISE identical to the XLA scatter
path (asserted across the parity matrix in tests/ops/test_pallas_blend.py).

Selection: opt-in via CHUNKFLOW_PALLAS=1 (unmeasured paths don't get to
be defaults — see pallas_mode); tests run it in interpret mode on CPU
(CHUNKFLOW_PALLAS=interpret); chip_smoke.py compiles it on the chip.
"""
from __future__ import annotations

from typing import Tuple

from chunkflow_tpu.core import envmode
from chunkflow_tpu.core.contracts import Spec, contract

Triple = Tuple[int, int, int]

_ON_VALUES = ("1", "on", "true", "force")
_OFF_VALUES = ("", "0", "off", "false", "no")
_MODE_CHOICES = {
    "off": _OFF_VALUES,
    "on": _ON_VALUES,
    "interpret": ("interpret",),
}
_WARNED_VALUES: set = set()


def pallas_mode() -> str:
    """'on' | 'off' | 'interpret' — resolved from env.

    An explicit truthy CHUNKFLOW_PALLAS ('1'/'on'/'force') selects the
    compiled kernel whatever the platform: 'on' never runs the
    interpreter or the XLA leg, and off a TPU it fails in Mosaic. Auto
    mode (unset env) resolves to OFF even on TPU.

    What the chip said (TPU v5 lite, jax 0.9.0, PR 21): as written since
    ISSUE 14 the kernel did NOT compile — "Mosaic failed to compile TPU
    kernel: cannot statically prove that index in dimension 0 is a
    multiple of 8" on the load at the dynamic (dy, dx) offset inside the
    scratch window. With the placement done by rotation
    (``place`` in :func:`fused_accumulate_patches`) it compiles at the
    production patch (20x256x256) and equals the XLA scatter leg bit
    for bit; chip_smoke.py holds that. Its speed against the scatter leg
    is not measured, so the measured-winner rule (docs/performance.md —
    never ship an unmeasured blend path as default) still applies.

    Unrecognized values resolve to OFF — a typo must not force-select the
    compiled Mosaic kernel on a CPU box — but warn ONCE on stderr
    (core/envmode.py holds the shared contract): a mistyped opt-in
    (``CHUNKFLOW_PALLAS=ture``) must not silently run the slow path
    either.

    ``CHUNKFLOW_FUSED_PIPELINE`` (ops/blend.py, ISSUE 17) outranks this
    knob: the fused patch pipeline IS the Pallas blend leg plus the
    Pallas gather leg composed, so pipeline 'on'/'interpret' force the
    matching mode here regardless of CHUNKFLOW_PALLAS — one knob flips
    the whole pipeline consistently instead of asking users to keep
    three envs in sync.
    """
    from chunkflow_tpu.ops import blend

    pipe = blend.fused_pipeline_mode()
    if pipe != "off":
        return "interpret" if pipe == "interpret" else "on"
    return envmode.resolve(
        "CHUNKFLOW_PALLAS", _MODE_CHOICES, default="off",
        note="treating it as OFF — the XLA scatter path runs, not the "
             "fused Pallas kernel",
        warned=_WARNED_VALUES,
    )


# Mosaic tiling of the two minor dims: DMA slice offsets into a tiled HBM
# memref must be *provably* divisible by these (round-1 hardware failure:
# "Failed to prove that a tile index in dimension 2 is divisible by the
# tiling (8)"). Patch strides carry no such guarantee, so the kernel only
# ever DMAs windows whose corners are rounded down to this alignment and
# places the patch at its (dy, dx) offset inside the VMEM window.
_SUBLANE = 8
_LANE = 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def padded_patch_shape(py: int, px: int) -> Tuple[int, int]:
    """(py_pad, px_pad): the aligned window that covers a (py, px) patch
    placed at any within-window offset (dy, dx) in [0,8) x [0,128)."""
    return (_round_up(py + _SUBLANE - 1, _SUBLANE),
            _round_up(px + _LANE - 1, _LANE))


def buffer_padding(pout: Triple) -> Tuple[int, int]:
    """Extra (Y, X) high-side padding the out/weight buffers need so every
    aligned window lies in bounds (worst case: a patch ending flush at the
    buffer edge whose aligned corner rounds down by up to 7/127)."""
    py_pad, px_pad = padded_patch_shape(pout[1], pout[2])
    return (py_pad - pout[1], px_pad - pout[2])


def fused_kernel_cost(B: int, co: int, pout: Triple) -> dict:
    """Analytic cost of one :func:`fused_accumulate_patches` build —
    the builder's own arithmetic, for ``profiling.stamp_cost`` and
    ``tools/kernel_report.py``. VMEM is the GL021 model: pipelined
    blocks double-buffered unless constant-index, plus scratch. Bytes
    are per whole grid; ``bytes_per_step`` is the worst (c == 0) step,
    which RMWs both the out and the weight window.

    Returns ``{grid_steps, vmem_bytes, bytes_per_step, bytes_accessed,
    flops}``.
    """
    pz, py, px = pout
    py_pad, px_pad = padded_patch_shape(py, px)
    tile = py * px * 4          # the streamed preds block (1,1,1,py,px)
    window = py_pad * px_pad * 4  # one aligned RMW window / the scratch
    vmem = (
        2 * tile              # preds block, dynamic index: double-buffered
        + pz * py * px * 4    # bump block, constant index: one copy
        + window              # VMEM scratch
    )
    grid_steps = B * co * pz
    # every step: read its preds tile + RMW one out window; the c == 0
    # step additionally RMWs the weight window
    bytes_accessed = (
        grid_steps * tile
        + B * (co + 1) * pz * window * 2
    )
    return {
        "grid_steps": grid_steps,
        "vmem_bytes": vmem,
        "bytes_per_step": tile + 4 * window,
        "bytes_accessed": bytes_accessed,
        "flops": B * (2 * co + 1) * pz * py * px,  # *bump, *valid, +acc
    }


@contract(
    out=Spec("co", "z", "y", "x", dtype="float32"),
    weight=Spec("z", "y", "x", dtype="float32"),
    preds=Spec("b", "co", "pz", "py", "px", dtype="float32"),
    valid=Spec("b", dtype="float32"),
    bump=Spec("pz", "py", "px", dtype="float32"),
    out_starts=Spec("b", 3, dtype="int32"),
)
def fused_accumulate_patches(out, weight, preds, valid, bump, out_starts,
                             pre_weighted: bool = False,
                             interpret: bool = False):
    """out[:, s:s+p] += preds[b]*bump*valid[b]; weight[s:s+p] +=
    bump*valid[b] for every b — weighting, placement and HBM RMW fused.

    out:      [co, Z, Y+pad, X+pad] f32  (donated, updated in place;
              padded per ``buffer_padding`` — caller crops afterwards)
    weight:   [Z, Y+pad, X+pad] f32      (donated, updated in place)
    preds:    [B, co, pz, py, px] f32 RAW engine predictions — or, with
              ``pre_weighted=True`` (the serving replay, whose forward
              program already applied ``bump*valid`` on another
              dispatch), the already-weighted stack, added as-is
    valid:    [B] f32 validity (0.0 for batch-padding rows)
    bump:     [pz, py, px] f32 — one constant-index block, VMEM-resident
              for the whole grid
    out_starts: [B, 3] int32 zyx corners (within-bounds, batch-padded)
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from chunkflow_tpu.testing import kernelcheck

    check = kernelcheck.active(interpret)
    B, co, pz, py, px = preds.shape
    py_pad, px_pad = padded_patch_shape(py, px)

    # Aligned window corner per patch + the patch's offset within it —
    # scalar work only; no per-patch tensor is materialized anymore.
    z0 = out_starts[:, 0]
    y0a = (out_starts[:, 1] // _SUBLANE) * _SUBLANE
    x0a = (out_starts[:, 2] // _LANE) * _LANE
    starts_aligned = jnp.stack([z0, y0a, x0a], axis=1)
    dyx = jnp.stack([out_starts[:, 1] - y0a, out_starts[:, 2] - x0a],
                    axis=1)
    # scalar-prefetch memory holds 32-bit scalars; 2D shape per the
    # Mosaic SMEM convention
    valid2 = valid.reshape(B, 1)

    def place(scratch, contrib, dy, dx):
        # Mosaic has no vector load/store at a dynamic unaligned (dy, dx)
        # ("cannot statically prove that index in dimension 0 is a
        # multiple of 8", v5e, jax 0.9.0), so the contribution is
        # zero-extended to the window and rotated into place; the window
        # exceeds the patch by at least (7, 127), so only zeros wrap
        # around, and x + 0.0 leaves the cells outside the patch
        # untouched (bitwise what scatter-add does for them)
        wide = jnp.concatenate(
            [contrib, jnp.zeros((py, px_pad - px), jnp.float32)], axis=1)
        wide = jnp.concatenate(
            [wide, jnp.zeros((py_pad - py, px_pad), jnp.float32)], axis=0)
        wide = pltpu.roll(pltpu.roll(wide, dy, 0), dx, 1)
        scratch[...] = scratch[...] + wide

    def kernel(starts_ref, dyx_ref, valid_ref, preds_ref, bump_ref,
               out_in, w_in, out_ref, w_ref, scratch, sem_in, sem_out):
        b = pl.program_id(0)
        c = pl.program_id(1)
        k = pl.program_id(2)
        if check:
            # the overlapping-RMW-order trace (patches must accumulate
            # ascending to match scatter_add) + the scratch canary: the
            # full-window load below overwrites the poison before any
            # read, so a clean kernel is bit-identical
            kernelcheck.observe_grid("fused_blend", b)
            kernelcheck.poison_scratch(scratch)
        z0 = starts_ref[b, 0]
        y0 = pl.multiple_of(starts_ref[b, 1], _SUBLANE)
        x0 = pl.multiple_of(starts_ref[b, 2], _LANE)
        dy = dyx_ref[b, 0]
        dx = dyx_ref[b, 1]
        v = valid_ref[b, 0]
        pred = preds_ref[0, 0, 0]   # [py, px], the raw tile
        bmp = bump_ref[k]           # [py, px] plane of the resident block

        # weighting in-kernel: same expression, same order, as the XLA
        # scatter leg's (preds * bump) * valid — bitwise equal f32 ops
        if pre_weighted:
            contrib = pred
        else:
            contrib = pred * bmp * v

        tile = out_ref.at[c, z0 + k, pl.ds(y0, py_pad), pl.ds(x0, px_pad)]
        load = pltpu.make_async_copy(tile, scratch, sem_in)
        load.start()
        load.wait()
        # placement fused into the RMW: add at the (dy, dx) offset inside
        # the VMEM window
        place(scratch, contrib, dy, dx)
        store = pltpu.make_async_copy(scratch, tile, sem_out)
        store.start()
        store.wait()

        @pl.when(c == 0)
        def _():
            wtile = w_ref.at[z0 + k, pl.ds(y0, py_pad), pl.ds(x0, px_pad)]
            wload = pltpu.make_async_copy(wtile, scratch, sem_in)
            wload.start()
            wload.wait()
            # the weight-patch contribution is computed in-register from
            # the resident bump block — no wpatch stack exists anymore
            place(scratch, bmp * v, dy, dx)
            wstore = pltpu.make_async_copy(scratch, wtile, sem_out)
            wstore.start()
            wstore.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, co, pz),
        in_specs=[
            # raw prediction tile, streamed per grid step at patch size
            pl.BlockSpec(
                (1, 1, 1, py, px),
                lambda b, c, k, *prefetch: (b, c, k, 0, 0),
            ),
            # the bump map as ONE constant-index block: fetched once,
            # VMEM-resident for the whole grid (the pipeline elides the
            # copy when the block index does not change)
            pl.BlockSpec(
                (pz, py, px),
                lambda b, c, k, *prefetch: (0, 0, 0),
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((py_pad, px_pad), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
    )

    if check:
        kernelcheck.check_bounds(
            starts_aligned, (pz, py_pad, px_pad), out.shape[1:],
            "fused_blend",
        )
    result = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(out.shape, out.dtype),
            jax.ShapeDtypeStruct(weight.shape, weight.dtype),
        ],
        # inputs (scalar-prefetch args count): starts_aligned 0, dyx 1,
        # valid 2, preds 3, bump 4, out 5, weight 6 -> alias out->output0,
        # weight->output1
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(starts_aligned, dyx, valid2, preds, bump, out, weight)
    if check:
        result = kernelcheck.check_result(result, "fused_blend")
    return result
