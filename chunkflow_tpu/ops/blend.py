"""The fused gather-forward-blend body shared by single- and multi-chip paths.

This is the pure function version of the hot loop (reference inferencer.py
:404-455 + chunk/base.py:792-807, redesigned as one XLA program): scan over
patch batches, vmap(dynamic_slice) gather, engine forward, then ONE
per-batch accumulation step — either a pair of runtime-coordinate
``lax.scatter_add`` ops (bump multiply on the XLA side) or, opt-in, the
fused Pallas kernel that does bump weighting, aligned-window placement and
the HBM read-modify-write in a single VMEM-resident pass
(ops/pallas_blend.py, ISSUE 14). ``Inferencer`` runs it per chip;
``parallel.engine`` shards the forward and replays the same accumulation.
"""
from __future__ import annotations

from typing import Callable, Tuple

from chunkflow_tpu.core.contracts import Spec, contract


def stack_budget_bytes() -> int:
    """Byte budget for patch stacks kept alive at once — a memory-fit
    gate shared by the (opt-in) stacked scatter path and the fold path so
    the two never diverge. Override with CHUNKFLOW_BLEND_STACK_MAX_GB.
    Default 4 GiB: ~1/4 of a v5e chip's 16 GB HBM, sized so the
    production-style 64x512x512 fold program (~2.4 GiB with its
    accumulation buffers) fits while jumbo 108x2048x2048 tasks (tens of
    GiB of stacks) fall back to per-batch scan accumulation."""
    import os

    return int(
        float(os.environ.get("CHUNKFLOW_BLEND_STACK_MAX_GB", "4")) * 2**30
    )


def stacked_scatter_enabled() -> bool:
    """Whether the stacked single-trailing-scatter accumulation may be
    selected. Default OFF: on the real chip the stacked path measured
    0.66 Mvox/s vs 1.48 for the per-batch scatter it replaced (the 36
    overlapping runtime-coordinate scatter windows serialize on TPU —
    docs/performance.md table), so the measured winner is the default and
    the stack is opt-in via CHUNKFLOW_BLEND_STACKED=1 for re-measurement."""
    import os

    return os.environ.get("CHUNKFLOW_BLEND_STACKED", "0").lower() not in (
        "0", "", "off", "false"
    )


_PIPELINE_CHOICES = {
    "off": ("", "0", "off", "false", "no"),
    "on": ("1", "on", "true", "force"),
    "interpret": ("interpret",),
}
_PIPELINE_WARNED: set = set()


def fused_pipeline_mode() -> str:
    """'off' | 'on' | 'interpret' — the ``CHUNKFLOW_FUSED_PIPELINE``
    knob (ISSUE 17): one device pipeline for the whole per-bucket patch
    step. The mode does not select a new mega-kernel; it FORCES the two
    proven kernel legs on at once — the Pallas gather front
    (``ops/pallas_gather.py``, ISSUE 15) and the fused bump-weighted
    accumulate (``ops/pallas_blend.py``, ISSUE 14) — and moves the
    serving packer's weighted-prediction stack device-resident
    (serve/packer.py), so the gathered-patch stack, the f32 activation
    stack and the weighted-prediction stack never round-trip HBM/host
    between stages. ``on`` compiles both Mosaic kernels (hardware);
    ``interpret`` runs them under the Pallas interpreter (+kernelcheck)
    on CPU — it IS the parity leg, not a throughput proxy. Default OFF
    per the measured-winner rule (docs/performance.md): its on-chip
    speed against the separate programs is not measured; its bit-identity
    with them is held by tests/inference/test_fused_pipeline.py.

    Resolution shares :func:`core.envmode.resolve` (warn-once; a typo
    must not force-select Mosaic kernels on a CPU box)."""
    from chunkflow_tpu.core import envmode

    return envmode.resolve(
        "CHUNKFLOW_FUSED_PIPELINE", _PIPELINE_CHOICES, default="off",
        note="treating it as OFF — the separately-selected gather/"
             "forward/blend programs run, not the fused patch pipeline",
        warned=_PIPELINE_WARNED,
    )


def pipeline_tag() -> str:
    """The fused-pipeline selection as a ProgramCache key component:
    ``""`` when off (keeps every historical key string byte-identical),
    else ``"pipe-on"`` / ``"pipe-interpret[+kc]"``. Joined — via
    :func:`pipeline_key` — into every program family the pipeline
    restructures (the per-chunk scatter program, all four serving
    programs, the sharded-engine programs), so a mid-stream
    ``CHUNKFLOW_FUSED_PIPELINE`` flip rebuilds instead of reusing a
    stale structure. The interpret tag carries the kernelcheck ``+kc``
    suffix while the sanitizer is live (its hooks are program
    identity), same convention as :func:`kernel_tag`."""
    mode = fused_pipeline_mode()
    if mode == "off":
        return ""
    if mode == "interpret":
        from chunkflow_tpu.testing import kernelcheck

        return f"pipe-interpret{kernelcheck.key_suffix()}"
    return f"pipe-{mode}"


def pipeline_key() -> tuple:
    """``()`` when the fused pipeline is off, else ``(pipeline_tag(),)``
    — the tuple callers concatenate onto ProgramCache keys (the same
    no-suffix-for-the-default convention as ``gather_key()``)."""
    tag = pipeline_tag()
    return (tag,) if tag else ()


def pipeline_kernel_cost(B: int, ci: int, co: int, pin, pout,
                         dtype="uint8") -> dict:
    """Analytic cost of one fused-pipeline patch step over a batch of
    ``B`` patches — the builders' own arithmetic composed
    (``pallas_gather.gather_kernel_cost`` +
    ``pallas_blend.fused_kernel_cost``), for ``profiling.stamp_cost``
    and ``tools/kernel_report.py``. The kernels run as sequential stages
    of one program, so VMEM is the max stage footprint, not the sum;
    ``bytes_accessed`` is the traffic the pipeline fundamentally moves
    (gather reads + the aligned-window RMW).

    ``hbm_intermediate_bytes`` is the inter-stage stack traffic the
    SEPARATE-programs composition pays and the pipeline does not: the
    gathered f32 patch stack and the weighted f32 prediction stack each
    written by one program and re-read by the next (x2 per stack). The
    fused pipeline's figure for the same workload is ~0 — patches and
    predictions stream through VMEM/registers between stages
    (docs/performance.md "The fused patch pipeline").
    """
    from chunkflow_tpu.ops import pallas_blend, pallas_gather

    pin = tuple(pin)
    pout = tuple(pout)
    gather = pallas_gather.gather_kernel_cost(B, ci, pin, dtype)
    blend = pallas_blend.fused_kernel_cost(B, co, pout)
    patch_stack_f32 = B * ci * pin[0] * pin[1] * pin[2] * 4
    pred_stack_f32 = B * co * pout[0] * pout[1] * pout[2] * 4
    return {
        "grid_steps": gather["grid_steps"] + blend["grid_steps"],
        "vmem_bytes": max(gather["vmem_bytes"], blend["vmem_bytes"]),
        "bytes_per_step": max(gather["bytes_per_step"],
                              blend["bytes_per_step"]),
        "bytes_accessed": gather["bytes_accessed"]
        + blend["bytes_accessed"],
        "flops": gather["flops"] + blend["flops"],
        # write + read of each inter-stage stack the separate-programs
        # composition materializes (the fusion's prize; ~0 fused)
        "hbm_intermediate_bytes": 2 * (patch_stack_f32 + pred_stack_f32),
    }


_REPLAY_CHOICES = {
    "sharded": ("", "1", "on", "sharded", "slab"),
    "replicated": ("0", "off", "replicated", "full"),
}
_REPLAY_WARNED: set = set()


def shard_replay_mode() -> str:
    """'sharded' | 'replicated' — the ``CHUNKFLOW_SHARD_REPLAY`` knob
    (ISSUE 19): how the mesh engine replays the reference blend
    accumulation. ``sharded`` (the default) replays each chip ONLY the
    windows that touch its output slab, into a slab+margin buffer —
    per-chip blend HBM drops from full-chunk to slab-sized, the path to
    chunks bigger than one chip (docs/multichip.md "Why every shape is
    bit-identical"). ``replicated`` is the historical PR 13 behavior:
    every chip ``all_gather``s the full weighted stack and replays every
    window into a full-chunk buffer — kept as the bisection/kill-switch
    leg.
    Re-read per chunk, like ``CHUNKFLOW_MESH`` itself."""
    from chunkflow_tpu.core import envmode

    return envmode.resolve(
        "CHUNKFLOW_SHARD_REPLAY", _REPLAY_CHOICES, default="sharded",
        note="running the sharded (slab) replay default — a typo must "
             "not silently select the full-chunk replicated replay",
        warned=_REPLAY_WARNED,
    )


def replay_tag() -> str:
    """The replay selection as a ProgramCache key component: ``""`` for
    the sharded default (the no-suffix-for-the-default convention),
    ``"replay-replicated"`` for the historical full-chunk replay."""
    mode = shard_replay_mode()
    return "" if mode == "sharded" else f"replay-{mode}"


def replay_key() -> tuple:
    """``()`` for the sharded-replay default, else ``(replay_tag(),)`` —
    concatenated onto the sharded-engine program keys so a mid-stream
    ``CHUNKFLOW_SHARD_REPLAY`` flip rebuilds instead of reusing a
    program with the wrong replay structure."""
    tag = replay_tag()
    return (tag,) if tag else ()


def kernel_tag() -> str:
    """The selected accumulation kernel as a ProgramCache key component:
    ``"scatter"`` (the XLA default) or ``"fused-on"`` /
    ``"fused-interpret"`` for the Pallas kernel. Every program family
    whose accumulation rides :func:`make_accumulate` folds this tag into
    its cache key, so flipping ``CHUNKFLOW_PALLAS`` mid-stream builds the
    right program instead of reusing a stale one (the same re-read-per-
    chunk convention as ``CHUNKFLOW_MESH``). The interpret tag carries
    the kernelcheck sanitizer's ``+kc`` suffix while it is live — its
    hooks change the traced program, so they are part of the program
    identity."""
    from chunkflow_tpu.ops import pallas_blend

    mode = pallas_blend.pallas_mode()
    if mode == "off":
        return "scatter"
    if mode == "interpret":
        from chunkflow_tpu.testing import kernelcheck

        return f"fused-interpret{kernelcheck.key_suffix()}"
    return f"fused-{mode}"


def make_accumulate(output_patch_size: Tuple[int, int, int], bump):
    """The ONE per-batch accumulation step, in two flavors sharing one
    kernel selection:

    ``accumulate(out, weight, preds, valid, starts) -> (out, weight)``
        takes RAW engine predictions; the bump-weight multiply
        (``preds * bump * valid``) and the weight-patch contribution
        (``bump * valid``) happen inside the step — on the XLA leg as
        elementwise ops feeding ``lax.scatter_add``, on the Pallas leg
        inside the fused kernel's VMEM pass (no weighted / weight-patch /
        padded stack is ever materialized).

    ``accumulate_weighted(out, weight, weighted, valid, starts)``
        takes an ALREADY-weighted stack (the serving packer's forward
        program and the sharded engine's all_gathered stacks apply
        ``bump*valid`` on their own dispatch); only the weight-buffer
        contribution ``bump * valid`` is computed inside.

    Returns ``(accumulate, accumulate_weighted, pad_y, pad_x)`` where
    ``(pad_y, pad_x)`` is the aligned-window buffer padding the Pallas
    kernel needs (zero on the XLA leg).

    Factored out of :func:`build_local_blend` so the serving packer's
    scatter program (chunkflow_tpu/serve/packer.py) and the sharded
    engine's replay (chunkflow_tpu/parallel/engine.py) run *exactly* the
    accumulation the fused per-chunk program runs — same kernel
    selection, same weighting expressions, same per-batch grouping —
    which is what makes packed-vs-per-chunk and mesh-vs-single outputs
    bit-identical.

    Both flavors trace under ``jax.named_scope("accumulate")`` (metadata
    only: core/profiling.py reads it back out of the compiled program,
    see :data:`~chunkflow_tpu.core.profiling.DEVICE_SCOPES`)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from chunkflow_tpu.ops import pallas_blend

    pout = tuple(output_patch_size)
    mode = pallas_blend.pallas_mode()
    pad_y, pad_x = (
        pallas_blend.buffer_padding(pout) if mode != "off" else (0, 0)
    )
    bump = jnp.asarray(bump)

    if mode != "off":
        interp = mode == "interpret"

        def accumulate(out, weight, preds, valid, starts):
            with jax.named_scope("accumulate"):
                return pallas_blend.fused_accumulate_patches(
                    out, weight, preds, valid, bump, starts,
                    pre_weighted=False, interpret=interp,
                )

        def accumulate_weighted(out, weight, weighted, valid, starts):
            with jax.named_scope("accumulate"):
                return pallas_blend.fused_accumulate_patches(
                    out, weight, weighted, valid, bump, starts,
                    pre_weighted=True, interpret=interp,
                )

        return accumulate, accumulate_weighted, pad_y, pad_x

    dnums4 = lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3, 4),
        inserted_window_dims=(),
        scatter_dims_to_operand_dims=(1, 2, 3),
    )
    dnums3 = lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3),
        inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0, 1, 2),
    )

    def _scatter(out, weight, weighted, wpatch, starts):
        out = lax.scatter_add(out, starts, weighted, dnums4)
        weight = lax.scatter_add(weight, starts, wpatch, dnums3)
        return out, weight

    def accumulate(out, weight, preds, valid, starts):
        # the same weighting expression, in the same order, the fused
        # kernel computes in VMEM — (preds * bump) * valid
        with jax.named_scope("accumulate"):
            weighted = preds * bump[None, None] \
                * valid[:, None, None, None, None]
            wpatch = bump[None] * valid[:, None, None, None]
            return _scatter(out, weight, weighted, wpatch, starts)

    def accumulate_weighted(out, weight, weighted, valid, starts):
        with jax.named_scope("accumulate"):
            wpatch = bump[None] * valid[:, None, None, None]
            return _scatter(out, weight, weighted, wpatch, starts)

    return accumulate, accumulate_weighted, pad_y, pad_x


def build_local_blend(
    forward: Callable,
    num_input_channels: int,
    num_output_channels: int,
    input_patch_size: Tuple[int, int, int],
    output_patch_size: Tuple[int, int, int],
    batch_size: int,
    bump,
):
    """Returns ``local_blend(chunk, in_starts, out_starts, valid, params)``
    -> (out, weight): weighted partial sums over the patches given (padded
    entries carry validity 0 and contribute nothing)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from chunkflow_tpu.ops import pallas_gather

    ci = num_input_channels
    co = num_output_channels
    pin = tuple(input_patch_size)
    pout = tuple(output_patch_size)

    # the shared per-batch accumulation step (and the (8,128)-aligned
    # buffer padding the pallas kernel needs, cropped after the scan)
    accumulate, _, pad_y, pad_x = make_accumulate(pout, bump)
    # the front half (ISSUE 15): the chunk arrives RAW (device-resident
    # once, narrow dtype) and the selected gather leg converts it —
    # whole-chunk f32 on the XLA legs (a no-op for the host front's
    # pre-converted f32 traffic, so CHUNKFLOW_GATHER=off runs the exact
    # historical program), per-tile in VMEM on the Pallas legs (the
    # full-chunk f32 materialization never exists in HBM). Callers fold
    # pallas_gather.gather_key() into the program key.
    prepare_chunk, gather_batch = pallas_gather.make_gather(ci, pin)

    # Stacking every prediction and accumulating ONCE (vs once per scan
    # batch) removes the per-batch full-buffer traffic on paper — but on
    # the real chip it measured 0.66 Mvox/s vs 1.48 for the per-batch
    # scatter (overlapping runtime-coordinate scatter windows serialize),
    # so it is OPT-IN (CHUNKFLOW_BLEND_STACKED=1) and additionally gated by
    # predicted stack size so jumbo chunks (e.g. 108x2048x2048 production
    # tasks) cannot OOM HBM even when opted in.
    stack_max_bytes = stack_budget_bytes()
    # the fused pipeline's whole point is that no whole-chunk prediction
    # stack exists between stages, so the stacked experiment cannot
    # compose with it — pipeline mode wins over CHUNKFLOW_BLEND_STACKED
    use_stacked = stacked_scatter_enabled() and fused_pipeline_mode() == "off"

    # Per-patch f32 bytes the stacked path keeps alive: the raw
    # prediction stack, plus (XLA leg only) the weighted copy and the
    # weight-patch stack the scatter consumes; the fused kernel
    # materializes neither, but the conservative bound is kept for both
    # legs so the budget decision cannot flip with the kernel selection.
    patch_bytes = (2 * co + 1) * pout[0] * pout[1] * pout[2] * 4

    @contract(
        chunk=Spec(None, "z", "y", "x"),
        in_starts=Spec("n", 3, dtype="int32"),
        out_starts=Spec("n", 3, dtype="int32"),
        valid=Spec("n", dtype="float32"),
    )
    def local_blend(chunk, in_starts, out_starts, valid, params):
        zyx = chunk.shape[1:]
        zyx_buf = (zyx[0], zyx[1] + pad_y, zyx[2] + pad_x)
        n = in_starts.shape[0]
        num_batches = n // batch_size
        with jax.named_scope("accumulate"):
            out0 = jnp.zeros((co,) + zyx_buf, dtype=jnp.float32)
            w0 = jnp.zeros(zyx_buf, dtype=jnp.float32)
        chunk_like = prepare_chunk(chunk)

        def forward_batch(b):
            i0 = b * batch_size
            s_in = lax.dynamic_slice(in_starts, (i0, 0), (batch_size, 3))
            patches = gather_batch(chunk_like, s_in)
            # RAW predictions: the bump*valid weighting lives inside the
            # accumulation step (fused into the kernel's VMEM pass on
            # the Pallas leg)
            with jax.named_scope("forward"):
                return forward(params, patches)

        if use_stacked and n * patch_bytes <= stack_max_bytes:
            _, all_preds = lax.scan(
                lambda c, b: (c, forward_batch(b)),
                None,
                jnp.arange(num_batches),
            )
            all_preds = all_preds.reshape((n, co) + pout)
            out, weight = accumulate(out0, w0, all_preds, valid, out_starts)
        else:
            def step(carry, b):
                out, weight = carry
                i0 = b * batch_size
                s_out = lax.dynamic_slice(
                    out_starts, (i0, 0), (batch_size, 3)
                )
                v = lax.dynamic_slice(valid, (i0,), (batch_size,))
                preds = forward_batch(b)
                out, weight = accumulate(out, weight, preds, v, s_out)
                return (out, weight), None

            (out, weight), _ = lax.scan(
                step, (out0, w0), jnp.arange(num_batches)
            )
        if pad_y or pad_x:
            with jax.named_scope("accumulate"):
                out = out[:, :, : zyx[1], : zyx[2]]
                weight = weight[:, : zyx[1], : zyx[2]]
        return out, weight

    return local_blend


@contract(
    out=Spec("co", "z", "y", "x", dtype="float32"),
    weight=Spec("z", "y", "x", dtype="float32"),
)
def normalize_blend(out, weight, dtype="float32"):
    """Reciprocal weight normalization; zero where nothing was predicted.
    ``dtype`` narrows the result inside the program (accumulation inputs
    stay float32) — the single place result dtype is decided for every
    program builder. ``uint8`` quantizes [0,1] maps exactly like the
    reference's save-time conversion (save_precomputed.py:90-92:
    ``chunk *= 255`` then truncating astype)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("normalize"):
        result = jnp.where(
            weight[None] > 0, out / jnp.maximum(weight[None], 1e-20), 0.0
        )
        if jnp.dtype(dtype) == jnp.uint8:
            return (jnp.clip(result, 0.0, 1.0) * 255.0).astype(jnp.uint8)
        return result.astype(jnp.dtype(dtype))
