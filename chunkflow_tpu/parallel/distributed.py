"""Patch-parallel psum program — the CROSS-HOST leg of the mesh engine.

The single-process patch-parallel path was subsumed by
:mod:`chunkflow_tpu.parallel.engine` (mesh spec ``data=N``), whose
forward-sharded + replayed-accumulation design is bitwise identical to
the single-device program. What remains here is the psum-merge variant
that the *multi-host* recipe still runs (``multihost.run_global``): when
one program spans processes, gathering every chip's weighted stack to
every host costs DCN bandwidth for data no host needs — the psum of
partial blend buffers is the right collective there, at ulp-level (not
bitwise) parity, which is exactly what the cross-host tests assert.

Cross-host: workers keep pulling independent chunk tasks from the queue
(communication-free task parallelism, deliberately preserved); this
module scales the single-task hot loop across the chips of a slice.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np

from chunkflow_tpu.core.compile_cache import ProgramCache


def make_mesh(n_devices: Optional[int] = None, axis: str = "data"):
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis,))


def build_sharded_program(
    engine_apply,
    num_input_channels: int,
    num_output_channels: int,
    input_patch_size,
    output_patch_size,
    batch_size: int,
    mesh,
    bump_array: np.ndarray,
    out_dtype="float32",
):
    """jit-compiled multi-chip fused inference: chunk + patch coords -> output.

    Patch arrays must be padded so N is divisible by (n_devices * batch_size)
    (use patching.pad_to_batch with that product). The chunk is replicated;
    each device scans its N/n_devices patches and psums partial buffers.
    The result is cast to ``out_dtype`` inside the program (accumulation
    stays float32).
    """
    import jax
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from chunkflow_tpu.ops.blend import build_local_blend, normalize_blend

    local_blend = build_local_blend(
        engine_apply,
        num_input_channels,
        num_output_channels,
        input_patch_size,
        output_patch_size,
        batch_size,
        bump_array,
    )

    def device_blend(chunk, in_starts, out_starts, valid, params):
        """Runs per device on its shard of the patch list; merges over ICI."""
        out, weight = local_blend(chunk, in_starts, out_starts, valid, params)
        out = lax.psum(out, "data")
        weight = lax.psum(weight, "data")
        return out, weight

    sharded = shard_map(
        device_blend,
        mesh=mesh,
        in_specs=(P(), P("data"), P("data"), P("data"), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )

    # chunk is donated (GL005): dead after the call, may be aliased into
    # the psum-merged output buffer — callers hand over a buffer they own
    @partial(jax.jit, donate_argnums=(0,))
    def program(chunk, in_starts, out_starts, valid, params):
        out, weight = sharded(chunk, in_starts, out_starts, valid, params)
        return normalize_blend(out, weight, out_dtype)

    return program


# compiled-program reuse across chunk tasks with identical geometry: a
# worker loop must pay the (multi-minute on a pod) XLA compile once, not
# per chunk. Keyed on engine identity + every shape that feeds tracing.
# A real ProgramCache (not the bare dict this module used to carry), so
# the cross-host programs get the same instrumentation — compile-time
# ledger, roofline accounting in programs.json — as every other family.
# Engines are pinned alive alongside their entry via _ENGINE_PINS so the
# id(engine) in the key cannot be recycled while the entry lives.
_PROGRAMS = ProgramCache(maxsize=16, label="distributed")
_ENGINE_PINS: dict = {}


def prepare_sharded(
    chunk_shape,
    engine,
    input_patch_size,
    output_patch_size,
    output_patch_overlap,
    batch_size: int,
    mesh,
):
    """Shared plumbing for the multi-host wrapper: patch grid + padded
    coordinate arrays + the (cached) compiled psum program. Returns
    (program, in_starts, out_starts, valid)."""
    from chunkflow_tpu.inference.bump import bump_map
    from chunkflow_tpu.inference.patching import enumerate_patches, pad_to_batch

    grid = enumerate_patches(
        tuple(chunk_shape), input_patch_size, output_patch_size,
        output_patch_overlap,
    )
    in_starts, out_starts, valid = pad_to_batch(
        grid, batch_size * mesh.devices.size
    )
    key = (
        id(engine), tuple(chunk_shape), tuple(input_patch_size),
        tuple(grid.output_patch_size), tuple(output_patch_overlap),
        batch_size, tuple(mesh.axis_names),
        tuple(d.id for d in mesh.devices.flat),
    )
    program = _PROGRAMS.get(
        key,
        lambda: build_sharded_program(
            engine.apply,
            engine.num_input_channels,
            engine.num_output_channels,
            input_patch_size,
            grid.output_patch_size,
            batch_size,
            mesh,
            bump_map(tuple(grid.output_patch_size)),
        ),
    )
    _ENGINE_PINS[key] = engine
    while len(_ENGINE_PINS) > 2 * _PROGRAMS.maxsize:
        _ENGINE_PINS.pop(next(iter(_ENGINE_PINS)))
    return program, in_starts, out_starts, valid


def sharded_inference(
    chunk_array: np.ndarray,
    engine,
    input_patch_size,
    output_patch_size,
    output_patch_overlap,
    batch_size: int = 1,
    mesh=None,
):
    """Single-process multi-chip inference — delegates to the unified
    engine (``data=N`` spec, bitwise identical to single-device)."""
    import jax

    from chunkflow_tpu.parallel.engine import (
        MeshSpec,
        sharded_inference as unified,
    )

    n_dev = (mesh.devices.size if mesh is not None
             else len(jax.local_devices()))
    return unified(
        chunk_array, engine, input_patch_size, output_patch_size,
        output_patch_overlap, batch_size=batch_size,
        spec=MeshSpec("data", (max(n_dev, 1),)),
    )
